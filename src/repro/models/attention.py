"""Attention: chunked flash-style training/prefill path + cached decode path.

Training/prefill uses a two-level loop with online softmax so activation
memory is O(chunk²) instead of O(s²); the inner fori_loop runs only over the
causally-reachable (and window-reachable) KV chunks — bounds may be traced,
so a scanned layer stack can mix local/global layers (gemma3 5:1) with a
per-layer window value.

``window`` convention: ``None`` (static) = no sliding window; otherwise an
int or traced scalar W meaning "attend to positions in (i-W, i]".

Decode attends one new token against a KV cache — either a full-length cache
with a validity mask, or a ring buffer of size W for sliding-window layers.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.dist.act_sharding import constrain

from .layers import init_linear, rope

__all__ = [
    "init_attn",
    "flash_attention",
    "attn_forward",
    "decode_attention",
    "attn_decode",
    "attn_decode_paged",
    "page_slots",
]

NEG_INF = -1e30


def init_attn(key, d, heads, kv, hd, dtype):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "wq": init_linear(k1, (d, heads, hd), dtype),
        "wk": init_linear(k2, (d, kv, hd), dtype),
        "wv": init_linear(k3, (d, kv, hd), dtype),
        "wo": init_linear(k4, (heads, hd, d), dtype),
    }


def flash_attention(
    q, k, v, *, causal: bool = True, window=None,
    q_chunk: int = 512, kv_chunk: int = 512, impl: str = "vjp",
):
    """q: (b, sq, h, hd); k, v: (b, skv, g, hd), h = g*r -> (b, sq, h, hd).

    impl:
      * "vjp" (training default): scan/fori forward + hand-written flash
        backward (recompute per chunk) — O(chunk²) live memory both ways and
        exact causal/window chunk skipping even with traced window values.
      * "scan" (prefill/inference): forward only; reverse-mode unsupported
        (traced loop bounds).
      * "unrolled" (the recorded §Perf BASELINE): statically unrolled
        autodiff path — backward saves every probability block (memory-
        hungry) and windows mask instead of skip.
    """
    b, sq, h, hd = q.shape
    _, skv, g, _ = k.shape
    r = h // g
    q_chunk = min(q_chunk, sq)
    kv_chunk = min(kv_chunk, skv)
    nq, nk = sq // q_chunk, skv // kv_chunk
    assert sq % q_chunk == 0 and skv % kv_chunk == 0, "pad sequences to chunks"
    if causal:
        assert q_chunk == kv_chunk and sq == skv, "causal path assumes alignment"

    if impl == "vjp":
        wv = jnp.asarray(window if window is not None else (1 << 40))
        return _flash_vjp(q, k, v, causal, window is not None, q_chunk,
                          kv_chunk, wv)
    if impl == "scan":
        out, _ = _flash_fwd_chunks(
            q, k, v, causal=causal, window=window,
            q_chunk=q_chunk, kv_chunk=kv_chunk,
        )
        return out
    assert impl == "unrolled", impl
    qg = q.reshape(b, sq, g, r, hd)
    scale = hd ** -0.5

    def make_kv_step(q_blk, qi):
        def kv_step(ki, carry):
            m, l, acc = carry
            k_blk = jax.lax.dynamic_slice_in_dim(k, ki * kv_chunk, kv_chunk, axis=1)
            v_blk = jax.lax.dynamic_slice_in_dim(v, ki * kv_chunk, kv_chunk, axis=1)
            s = jnp.einsum(
                "bqgrd,bkgd->bgrqk", q_blk, k_blk,
                preferred_element_type=jnp.float32,
            )
            if causal or window is not None:
                ipos = qi * q_chunk + jnp.arange(q_chunk)
                jpos = ki * kv_chunk + jnp.arange(kv_chunk)
                mask = jnp.ones((q_chunk, kv_chunk), bool)
                if causal:
                    mask &= jpos[None, :] <= ipos[:, None]
                if window is not None:
                    mask &= jpos[None, :] > ipos[:, None] - window
                s = jnp.where(mask, s, NEG_INF)
            m2 = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m2[..., None])
            corr = jnp.exp(m - m2)
            l2 = l * corr + p.sum(-1)
            pv = jnp.einsum(
                "bgrqk,bkgd->bgrqd", p.astype(v.dtype), v_blk,
                preferred_element_type=jnp.float32,
            )
            return m2, l2, acc * corr[..., None] + pv

        return kv_step

    def init_acc():
        return (
            jnp.full((b, g, r, q_chunk), NEG_INF, jnp.float32),
            jnp.zeros((b, g, r, q_chunk), jnp.float32),
            jnp.zeros((b, g, r, q_chunk, hd), jnp.float32),
        )

    def finish(acc_tuple):
        m, l, acc = acc_tuple
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return out.transpose(0, 3, 1, 2, 4).reshape(b, q_chunk, h, hd).astype(q.dtype)

    blocks = []
    for qi in range(nq):  # static unroll: static fori_loop bounds
        q_blk = (qg[:, qi * q_chunk : (qi + 1) * q_chunk] * scale).astype(q.dtype)
        hi = qi + 1 if causal else nk
        acc = jax.lax.fori_loop(0, hi, make_kv_step(q_blk, qi), init_acc())
        blocks.append(finish(acc))
    return jnp.concatenate(blocks, axis=1)


def _flash_fwd_chunks(q, k, v, *, causal, window, q_chunk, kv_chunk):
    """Shared forward: returns (out, lse) with lse: (b, g, r, sq).

    scan over q chunks; inner fori_loop bounds may be traced (window can be
    a per-layer traced scalar) — legal here because gradients flow through
    the hand-written VJP below, never through this loop.
    """
    b, sq, h, hd = q.shape
    _, skv, g, _ = k.shape
    r = h // g
    nq, nk = sq // q_chunk, skv // kv_chunk
    qg = q.reshape(b, sq, g, r, hd)
    scale = hd ** -0.5

    def bounds(qi):
        if causal:
            hi = qi + 1
            lo = (
                jnp.maximum(0, (qi * q_chunk - window) // kv_chunk)
                if window is not None
                else 0
            )
        else:
            lo, hi = 0, nk
        return lo, hi

    def mask_for(qi, ki):
        if not (causal or window is not None):
            return None
        ipos = qi * q_chunk + jnp.arange(q_chunk)
        jpos = ki * kv_chunk + jnp.arange(kv_chunk)
        mask = jnp.ones((q_chunk, kv_chunk), bool)
        if causal:
            mask &= jpos[None, :] <= ipos[:, None]
        if window is not None:
            mask &= jpos[None, :] > ipos[:, None] - window
        return mask

    def q_step(_, qi):
        q_blk = jax.lax.dynamic_slice_in_dim(qg, qi * q_chunk, q_chunk, axis=1)
        q_blk = (q_blk * scale).astype(q.dtype)
        lo, hi = bounds(qi)

        def kv_step(ki, carry):
            m, l, acc = carry
            k_blk = jax.lax.dynamic_slice_in_dim(k, ki * kv_chunk, kv_chunk, axis=1)
            v_blk = jax.lax.dynamic_slice_in_dim(v, ki * kv_chunk, kv_chunk, axis=1)
            s = jnp.einsum("bqgrd,bkgd->bgrqk", q_blk, k_blk,
                           preferred_element_type=jnp.float32)
            msk = mask_for(qi, ki)
            if msk is not None:
                s = jnp.where(msk, s, NEG_INF)
            m2 = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m2[..., None])
            corr = jnp.exp(m - m2)
            l2 = l * corr + p.sum(-1)
            pv = jnp.einsum("bgrqk,bkgd->bgrqd", p.astype(v.dtype), v_blk,
                            preferred_element_type=jnp.float32)
            return m2, l2, acc * corr[..., None] + pv

        m0 = jnp.full((b, g, r, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, g, r, q_chunk), jnp.float32)
        a0 = jnp.zeros((b, g, r, q_chunk, hd), jnp.float32)
        m, l, acc = jax.lax.fori_loop(lo, hi, kv_step, (m0, l0, a0))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        out = out.transpose(0, 3, 1, 2, 4).reshape(b, q_chunk, h, hd)
        lse = m + jnp.log(jnp.maximum(l, 1e-30))
        return None, (out.astype(q.dtype), lse)

    _, (blocks, lses) = jax.lax.scan(q_step, None, jnp.arange(nq))
    out = blocks.transpose(1, 0, 2, 3, 4).reshape(b, sq, h, hd)
    lse = jnp.moveaxis(lses, 0, 3).reshape(b, g, r, sq)
    return out, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_vjp(q, k, v, causal, has_window, q_chunk, kv_chunk, window_val):
    out, _ = _flash_fwd_chunks(
        q, k, v, causal=causal,
        window=window_val if has_window else None,
        q_chunk=q_chunk, kv_chunk=kv_chunk,
    )
    return out


def _flash_vjp_fwd(q, k, v, causal, has_window, q_chunk, kv_chunk, window_val):
    out, lse = _flash_fwd_chunks(
        q, k, v, causal=causal,
        window=window_val if has_window else None,
        q_chunk=q_chunk, kv_chunk=kv_chunk,
    )
    return out, (q, k, v, out, lse, window_val)


def _flash_vjp_bwd(causal, has_window, q_chunk, kv_chunk, res, dout):
    """Flash backward: recompute p chunk-by-chunk; O(chunk²) live memory.

        delta_i = Σ_d dO_id · O_id
        p_ij    = exp(s_ij − lse_i)
        dv_j    = Σ_i p_ij dO_i          dp_ij = dO_i · v_j
        ds_ij   = p_ij (dp_ij − delta_i)
        dq_i    = scale Σ_j ds_ij k_j     dk_j = scale Σ_i ds_ij q_i
    """
    q, k, v, out, lse, window_val = res
    window = window_val if has_window else None
    b, sq, h, hd = q.shape
    _, skv, g, _ = k.shape
    r = h // g
    nq, nk = sq // q_chunk, skv // kv_chunk
    qg = q.reshape(b, sq, g, r, hd)
    og = out.reshape(b, sq, g, r, hd)
    dog = dout.reshape(b, sq, g, r, hd)
    scale = hd ** -0.5
    delta = jnp.einsum(
        "bsgrd,bsgrd->bgrs", dog.astype(jnp.float32), og.astype(jnp.float32)
    )

    def q_step(carry, qi):
        dk_acc, dv_acc = carry
        q_blk = jax.lax.dynamic_slice_in_dim(qg, qi * q_chunk, q_chunk, axis=1)
        do_blk = jax.lax.dynamic_slice_in_dim(dog, qi * q_chunk, q_chunk, axis=1)
        lse_blk = jax.lax.dynamic_slice_in_dim(lse, qi * q_chunk, q_chunk, axis=3)
        dl_blk = jax.lax.dynamic_slice_in_dim(delta, qi * q_chunk, q_chunk, axis=3)
        if causal:
            hi = qi + 1
            lo = (
                jnp.maximum(0, (qi * q_chunk - window) // kv_chunk)
                if window is not None
                else 0
            )
        else:
            lo, hi = 0, nk

        def kv_step(ki, inner):
            dq_blk, dk_acc, dv_acc = inner
            k_blk = jax.lax.dynamic_slice_in_dim(k, ki * kv_chunk, kv_chunk, axis=1)
            v_blk = jax.lax.dynamic_slice_in_dim(v, ki * kv_chunk, kv_chunk, axis=1)
            s = jnp.einsum("bqgrd,bkgd->bgrqk", q_blk, k_blk,
                           preferred_element_type=jnp.float32) * scale
            if causal or window is not None:
                ipos = qi * q_chunk + jnp.arange(q_chunk)
                jpos = ki * kv_chunk + jnp.arange(kv_chunk)
                mask = jnp.ones((q_chunk, kv_chunk), bool)
                if causal:
                    mask &= jpos[None, :] <= ipos[:, None]
                if window is not None:
                    mask &= jpos[None, :] > ipos[:, None] - window
                s = jnp.where(mask, s, NEG_INF)
            p = jnp.exp(s - lse_blk[..., None])                     # (b,g,r,q,k)
            dv = jnp.einsum("bgrqk,bqgrd->bkgd", p,
                            do_blk.astype(jnp.float32))
            dp = jnp.einsum("bqgrd,bkgd->bgrqk",
                            do_blk.astype(jnp.float32),
                            v_blk.astype(jnp.float32))
            ds = p * (dp - dl_blk[..., None])
            dq_blk = dq_blk + scale * jnp.einsum(
                "bgrqk,bkgd->bqgrd", ds, k_blk.astype(jnp.float32))
            dk = scale * jnp.einsum("bgrqk,bqgrd->bkgd", ds,
                                    q_blk.astype(jnp.float32))
            dk_acc = jax.lax.dynamic_update_slice_in_dim(
                dk_acc,
                jax.lax.dynamic_slice_in_dim(dk_acc, ki * kv_chunk, kv_chunk, 1) + dk,
                ki * kv_chunk, axis=1)
            dv_acc = jax.lax.dynamic_update_slice_in_dim(
                dv_acc,
                jax.lax.dynamic_slice_in_dim(dv_acc, ki * kv_chunk, kv_chunk, 1) + dv,
                ki * kv_chunk, axis=1)
            return dq_blk, dk_acc, dv_acc

        dq0 = jnp.zeros((b, q_chunk, g, r, hd), jnp.float32)
        dq_blk, dk_acc, dv_acc = jax.lax.fori_loop(
            lo, hi, kv_step, (dq0, dk_acc, dv_acc)
        )
        return (dk_acc, dv_acc), dq_blk

    dk0 = jnp.zeros((b, skv, g, hd), jnp.float32)
    dv0 = jnp.zeros((b, skv, g, hd), jnp.float32)
    (dk, dv), dq_blocks = jax.lax.scan(q_step, (dk0, dv0), jnp.arange(nq))
    dq = dq_blocks.transpose(1, 0, 2, 3, 4, 5).reshape(b, sq, h, hd)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            jnp.zeros_like(window_val))


_flash_vjp.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def attn_forward(
    p, x, positions, *, heads, kv, hd, theta, causal=True, window=None,
    enc=None, q_chunk=512, kv_chunk=512, return_kv=False,
    impl="vjp",
):
    """Project -> rope -> attend -> project.  ``enc`` switches to cross
    attention against encoder states (no rope on keys)."""
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    src = x if enc is None else enc
    k = jnp.einsum("bsd,dgk->bsgk", src, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dgk->bsgk", src, p["wv"].astype(dt))
    # heads claim 'model' when divisible; otherwise the batch spreads over
    # data AND model (batch-parallel attention — no replicated compute).
    q = constrain(q, "?batch_plus", None, "heads", None)
    k = constrain(k, "?batch_plus", None, "kv", None)
    v = constrain(v, "?batch_plus", None, "kv", None)
    q = rope(q, positions, theta)
    if enc is None:
        k = rope(k, positions, theta)
    o = flash_attention(
        q, k, v, causal=causal and enc is None, window=window,
        q_chunk=q_chunk, kv_chunk=kv_chunk, impl=impl,
    )
    o = constrain(o, "?batch_plus", None, "heads", None)
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(dt))
    return (constrain(out, "batch", None, None), (k, v)) if return_kv else constrain(out, "batch", None, None)


def decode_attention(q, k_cache, v_cache, cur_len, *, window=None,
                     kscale=None, vscale=None):
    """Cached attention for one or more appended tokens over a linear cache.

    q: (b, sq, h, hd); caches: (b, S, g, hd); cur_len: tokens in cache
    including the newest — a scalar, or per-row ``(b,)`` when rows sit at
    different sequence positions (the continuous-batching slot layout,
    DESIGN.md §12).  Query i (of sq) lives at position cur_len - sq + i and
    attends causally: slots >= its position + 1 (and outside the window)
    are masked.  sq == 1 with scalar cur_len is the classic decode step;
    sq > 1 is the chunked prefill-extend path.

    int8-quantized caches pass kscale/vscale (b, g): HBM reads stay int8 and
    the per-(batch, kv-head) scale folds in AFTER the contraction.
    """
    b, S, g, hd = k_cache.shape
    sq, h = q.shape[1], q.shape[2]
    r = h // g
    cd = q.dtype if kscale is not None else k_cache.dtype
    qg = q.reshape(b, sq, g, r, hd) * (hd ** -0.5)
    s = jnp.einsum(
        "bqgrd,bkgd->bgrqk", qg.astype(cd), k_cache.astype(cd),
        preferred_element_type=jnp.float32,
    )
    if kscale is not None:
        s = s * kscale[:, :, None, None, None]
    jpos = jnp.arange(S)
    # qpos: (b|1, sq) position of each query row/token; scalar cur_len
    # reshapes to (1, 1) and broadcasts exactly like the historical path.
    qpos = jnp.reshape(jnp.asarray(cur_len), (-1, 1)) - sq + jnp.arange(sq)
    mask = jpos[None, None, :] <= qpos[..., None]
    if window is not None:
        mask &= jpos[None, None, :] > qpos[..., None] - window
    s = jnp.where(mask[:, None, None, :, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum(
        "bgrqk,bkgd->bgrqd", p.astype(cd), v_cache.astype(cd),
        preferred_element_type=jnp.float32,
    )
    if vscale is not None:
        o = o * vscale[:, :, None, None, None]
    return o.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, hd).astype(q.dtype)


def decode_attention_ring(q, k_cache, v_cache, pos):
    """Sliding-window decode over a ring buffer of size W; newest token was
    just written at slot pos % W.  Valid slots: logical position >= 0."""
    b, W, g, hd = k_cache.shape
    h = q.shape[2]
    r = h // g
    qg = q.reshape(b, 1, g, r, hd) * (hd ** -0.5)
    s = jnp.einsum(
        "bqgrd,bkgd->bgrqk", qg.astype(k_cache.dtype), k_cache,
        preferred_element_type=jnp.float32,
    )
    slots = jnp.arange(W)
    logical = pos - jnp.mod(pos - slots, W)  # logical position held by slot
    mask = logical >= 0
    s = jnp.where(mask[None, None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum(
        "bgrqk,bkgd->bgrqd", p.astype(v_cache.dtype), v_cache,
        preferred_element_type=jnp.float32,
    )
    return o.transpose(0, 3, 1, 2, 4).reshape(b, 1, h, hd).astype(q.dtype)


def attn_decode(
    p, x, cache, pos, *, heads, kv, hd, theta, ring=False, window=None, enc=None
):
    """Cached decode for one block: one token, a chunk, per-row positions.

    cache: {"k": (b,S,g,hd), "v": ...} (S = window size when ring=True),
    optionally int8 with "ks"/"vs" (b, g) dequant scales.

    x is (b, s, d) with s >= 1 new tokens per row; ``pos`` is the logical
    position of the FIRST new token — a scalar (all rows aligned, the
    historical decode step) or a ``(b,)`` vector (each row at its own
    position: the continuous-batching slot layout, DESIGN.md §12).  s > 1
    is the chunked prefill-extend path: tokens land at pos..pos+s-1 with
    causal attention inside the chunk.  Ring (sliding-window) caches and
    cross-attention (enc != None) support the classic scalar/s==1 call
    only.  Cross-attention blocks have no cache to update.
    """
    dt = x.dtype
    b, s, _ = x.shape
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    pos_arr = jnp.asarray(pos)
    per_row = pos_arr.ndim > 0
    positions = jnp.broadcast_to(
        jnp.reshape(pos_arr, (-1, 1)) + jnp.arange(s)[None, :], (b, s)
    )
    if enc is not None:
        assert s == 1 and not per_row, "cross-attention decode is one-token"
        k = jnp.einsum("bsd,dgk->bsgk", enc, p["wk"].astype(dt))
        v = jnp.einsum("bsd,dgk->bsgk", enc, p["wv"].astype(dt))
        q = rope(q, positions, theta)
        o = decode_attention(q, k, v, k.shape[1])
        return jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(dt)), cache
    k_new = jnp.einsum("bsd,dgk->bsgk", x, p["wk"].astype(dt))
    v_new = jnp.einsum("bsd,dgk->bsgk", x, p["wv"].astype(dt))
    q = rope(q, positions, theta)
    k_new = rope(k_new, positions, theta)
    quant = "ks" in cache
    if quant:
        # quantize the incoming token with the prefill scales (fixed-scale
        # drift caveat documented in EXPERIMENTS §Perf)
        k_new = jnp.clip(
            jnp.round(k_new / cache["ks"][:, None, :, None]), -127, 127
        )
        v_new = jnp.clip(
            jnp.round(v_new / cache["vs"][:, None, :, None]), -127, 127
        )
    S = cache["k"].shape[1]
    if per_row:
        assert not ring, "per-row positions need a linear (non-ring) cache"
        # each row writes its s new tokens at its own offset
        row_update = jax.vmap(
            lambda c, u, st: jax.lax.dynamic_update_slice_in_dim(
                c, u, st, axis=0
            )
        )
        starts = pos_arr.astype(jnp.int32)
        kc = row_update(cache["k"], k_new.astype(cache["k"].dtype), starts)
        vc = row_update(cache["v"], v_new.astype(cache["v"].dtype), starts)
    else:
        slot = jnp.mod(pos, S) if ring else pos
        kc = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], k_new.astype(cache["k"].dtype), slot, axis=1
        )
        vc = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], v_new.astype(cache["v"].dtype), slot, axis=1
        )
    if ring:
        assert s == 1, "ring caches decode one token at a time"
        o = decode_attention_ring(q, kc, vc, pos)
    else:
        o = decode_attention(
            q, kc, vc, pos_arr + s, window=window,
            kscale=cache.get("ks"), vscale=cache.get("vs"),
        )
    out_cache = {"k": kc, "v": vc}
    if quant:
        out_cache["ks"], out_cache["vs"] = cache["ks"], cache["vs"]
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(dt)), out_cache


def page_slots(pages, positions, page_size, valid_len=None, scratch=None):
    """(physical page, in-page offset), each (b, s), that the new tokens at
    ``positions`` (b, s) write to through the page table ``pages``; with
    ``valid_len``/``scratch`` the padded write barrier sends tokens past
    each row's ``valid_len`` to its ``scratch`` page instead."""
    s = positions.shape[1]
    n_pg_tab = pages.shape[1]
    lp = jnp.clip(positions // page_size, 0, n_pg_tab - 1)  # pads may be OOB
    pid = jnp.take_along_axis(pages, lp, axis=1)  # (b,s)
    off = positions % page_size
    if valid_len is not None:
        # padded write barrier: pad rows (i >= valid_len) scatter into the
        # per-row scratch page instead of through the table
        keep = jnp.arange(s)[None, :] < jnp.reshape(
            jnp.asarray(valid_len, jnp.int32), (-1, 1))
        pid = jnp.where(keep, pid, jnp.reshape(
            jnp.asarray(scratch, jnp.int32), (-1, 1)))
    return pid, off


def attn_decode_paged(
    p, x, k_pool, v_pool, pages, pos, *, page_size, heads, kv, hd, theta,
    window=None, valid_len=None, scratch=None,
):
    """Cached decode through a page-table indirection (DESIGN.md §13).

    Instead of one contiguous row per request, KV lives in a pooled buffer
    of fixed-size pages — ``k_pool``/``v_pool``: (P, page_size, g, hd) —
    and each row of ``pages`` (an int32 ``(b, n_pg)`` table, plain data so
    remapping never retraces) names the physical pages that back the row's
    logical positions 0..n_pg*page_size-1 in order.  Unmapped entries
    point at the reserved parking page 0.

    x: (b, s, d) with s >= 1 new tokens per row at per-row positions
    ``pos`` (b,).  The s new K/V project+rope exactly like ``attn_decode``
    and SCATTER to (page, offset) = (pos+i) divmod page_size through the
    table; reads GATHER the table back into a (b, n_pg*page_size, g, hd)
    logical row and reuse ``decode_attention`` unchanged.  Because
    n_pg*page_size == cache_len, the gathered row has the same length
    and ordering as a linear cache row — junk in parked/unwritten pages
    sits behind the same NEG_INF mask that hides unwritten cache zeros,
    so outputs equal the linear path's up to float32 rounding (the two
    are different compiled graphs; tests hold them to a tolerance).  No
    ring/quant/cross-attention support (the serve engine lowers or gates
    those before reaching here).

    ``valid_len``/``scratch`` (both traced int32 DATA, so one graph per
    token-shape still serves every call) implement the padded write
    barrier for bucketed prefill: per row, only the first ``valid_len``
    of the s tokens write through the page table — the rest scatter into
    the row's ``scratch`` page, a throwaway physical page the caller
    frees right after the call.  Pad K/V never lands in a shared,
    registered, or retained page, so CoW/fingerprint invariants hold
    without inspecting pad content.  Pad positions may also run past the
    logical row (start+s > n_pg*page_size); their table lookup is clipped
    in-bounds and then discarded by the same mask.  Pad QUERIES still
    attend (their outputs are junk) — the caller's ``logit_index`` reads
    the last real position, and causal masking keeps real queries from
    ever seeing a pad key, because pad keys only exist in the scratch
    page which no table row names.
    """
    dt = x.dtype
    b, s, _ = x.shape
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    pos_arr = jnp.asarray(pos)
    positions = jnp.broadcast_to(
        jnp.reshape(pos_arr, (-1, 1)) + jnp.arange(s)[None, :], (b, s)
    )
    k_new = jnp.einsum("bsd,dgk->bsgk", x, p["wk"].astype(dt))
    v_new = jnp.einsum("bsd,dgk->bsgk", x, p["wv"].astype(dt))
    q = rope(q, positions, theta)
    k_new = rope(k_new, positions, theta)
    pid, off = page_slots(pages, positions, page_size, valid_len, scratch)
    k_pool = k_pool.at[pid, off].set(k_new.astype(k_pool.dtype))
    v_pool = v_pool.at[pid, off].set(v_new.astype(v_pool.dtype))
    n_pg = pages.shape[1]
    k_rows = k_pool[pages].reshape(b, n_pg * page_size, kv, hd)
    v_rows = v_pool[pages].reshape(b, n_pg * page_size, kv, hd)
    o = decode_attention(q, k_rows, v_rows, pos_arr + s, window=window)
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(dt)), k_pool, v_pool
