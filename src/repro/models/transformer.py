"""Decoder-only transformer stacks (dense / moe / vlm) and the whisper-style
encoder-decoder — init, training forward, prefill, and decode.

Layers are scanned (stacked parameter pytrees) so the HLO stays O(1) in
depth; heterogeneity (gemma3's 5:1 local:global pattern) rides through the
scan as a per-layer flag driving a *traced* window value.  Leading dense
layers before the expert layers (``cfg.first_dense``, DeepSeek-V3's
``first_k_dense_replace``) are a stack of their own, ``dense_layers``,
scanned before ``layers``; caches keep one leaf of all layers.  Activation
checkpointing wraps the scan body when cfg.remat.  The attention of every
layer is ``cfg.attn``: per-head K/V (``attention.py``) or latent
(``mla.py``); the head is ``params["embed"]`` when tied, else
``params["head"]``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.dist.act_sharding import constrain

from .attention import attn_decode, attn_decode_paged, attn_forward, init_attn
from .config import ModelConfig
from .layers import embed, gated_mlp, init_linear, init_mlp, init_norm, rms_norm, unembed
from .mla import init_mla, mla_decode, mla_decode_paged, mla_forward
from .moe import init_moe, moe_forward

NO_WINDOW = 1 << 40  # "infinite" traced window for global layers


# --------------------------------------------------------------------- util
def _dtype(cfg: ModelConfig):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


def _pdtype(cfg: ModelConfig):
    return jnp.bfloat16 if cfg.param_dtype == "bfloat16" else jnp.float32


def global_flags(cfg: ModelConfig) -> np.ndarray:
    """(L,) bool: True where the layer is global-attention (gemma3 5:1)."""
    if not cfg.window:
        return np.ones(cfg.n_layers, dtype=bool)
    return np.asarray(
        [(i % cfg.global_every) == cfg.global_every - 1 for i in range(cfg.n_layers)]
    )


def layer_window(cfg, is_global):
    """Traced per-layer window value (None when the arch has no windows)."""
    if not cfg.window:
        return None
    return jnp.where(is_global, jnp.int64(NO_WINDOW), jnp.int64(cfg.window))


def _segments(cfg: ModelConfig):
    """(params key, expert layer?, first layer, end) of each scanned layer
    stack, in order."""
    moe, nd, L = cfg.family == "moe", cfg.first_dense, cfg.n_layers
    if nd:
        return (("dense_layers", False, 0, nd), ("layers", moe, nd, L))
    return (("layers", moe, 0, L),)


def _head(cfg: ModelConfig, params):
    return params["embed"] if cfg.tie_embeddings else params["head"]


def _cache_names(cfg: ModelConfig) -> tuple[str, ...]:
    """The per-layer cache leaves of the attention kind."""
    return ("c_kv", "k_pe") if cfg.attn == "mla" else ("k", "v")


def _ffn(cfg, pl, h, moe: bool):
    """The block's feed-forward: (y, aux loss)."""
    if moe:
        return moe_forward(pl["moe"], cfg, h)
    return gated_mlp(h, pl["mlp"]["wi"], pl["mlp"]["wo"], cfg.act), 0.0


def _maybe_remat(f, cfg):
    if not cfg.remat:
        return f
    policy = (
        jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        if cfg.remat_policy == "dots"
        else None
    )
    return jax.checkpoint(f, prevent_cse=False, policy=policy)


# --------------------------------------------------------------------- init
def init_dense_block(key, cfg: ModelConfig, dt, moe: bool):
    k1, k2 = jax.random.split(key)
    p = {
        "ln1": init_norm((cfg.d_model,), dt),
        "attn": (init_mla(k1, cfg, dt) if cfg.attn == "mla" else
                 init_attn(k1, cfg.d_model, cfg.n_heads, cfg.n_kv,
                           cfg.head_dim, dt)),
        "ln2": init_norm((cfg.d_model,), dt),
    }
    if moe:
        p["moe"] = init_moe(k2, cfg, dt)
    else:
        p["mlp"] = init_mlp(k2, cfg.d_model, cfg.d_ff, dt)
    return p


def init_decoder_only(key, cfg: ModelConfig):
    """Layer i is drawn from ``split(kL, n_layers)[i]`` whatever its kind;
    an untied head from ``fold_in(kE, 1)``."""
    dt = _pdtype(cfg)
    kE, kL = jax.random.split(key)
    layer_keys = jax.random.split(kL, cfg.n_layers)
    p = {"embed": init_linear(kE, (cfg.vocab, cfg.d_model), dt, scale=0.02),
         "final_norm": init_norm((cfg.d_model,), dt)}
    for name, moe, lo, hi in _segments(cfg):
        keys = layer_keys if hi - lo == cfg.n_layers else layer_keys[lo:hi]
        p[name] = jax.vmap(lambda k: init_dense_block(k, cfg, dt, moe))(keys)
    if not cfg.tie_embeddings:
        p["head"] = init_linear(jax.random.fold_in(kE, 1),
                                (cfg.vocab, cfg.d_model), dt)
    return p


def init_encdec(key, cfg: ModelConfig):
    dt = _pdtype(cfg)
    kE, kEnc, kDec = jax.random.split(key, 3)

    def enc_block(k):
        k1, k2 = jax.random.split(k)
        return {
            "ln1": init_norm((cfg.d_model,), dt),
            "attn": init_attn(k1, cfg.d_model, cfg.n_heads, cfg.n_kv, cfg.head_dim, dt),
            "ln2": init_norm((cfg.d_model,), dt),
            "mlp": init_mlp(k2, cfg.d_model, cfg.d_ff, dt),
        }

    def dec_block(k):
        k1, k2, k3 = jax.random.split(k, 3)
        return {
            "ln1": init_norm((cfg.d_model,), dt),
            "self_attn": init_attn(
                k1, cfg.d_model, cfg.n_heads, cfg.n_kv, cfg.head_dim, dt
            ),
            "ln2": init_norm((cfg.d_model,), dt),
            "cross_attn": init_attn(
                k2, cfg.d_model, cfg.n_heads, cfg.n_kv, cfg.head_dim, dt
            ),
            "ln3": init_norm((cfg.d_model,), dt),
            "mlp": init_mlp(k3, cfg.d_model, cfg.d_ff, dt),
        }

    return {
        "embed": init_linear(kE, (cfg.vocab, cfg.d_model), dt, scale=0.02),
        "enc_layers": jax.vmap(enc_block)(jax.random.split(kEnc, cfg.enc_layers)),
        "enc_norm": init_norm((cfg.d_model,), dt),
        "dec_layers": jax.vmap(dec_block)(jax.random.split(kDec, cfg.n_layers)),
        "final_norm": init_norm((cfg.d_model,), dt),
    }


# ----------------------------------------------------------------- forward
def _attn_kwargs(cfg):
    return dict(
        heads=cfg.n_heads, kv=cfg.n_kv, hd=cfg.head_dim, theta=cfg.rope_theta
    )


def decoder_stack(cfg: ModelConfig, params, x, positions, *, collect_kv=False):
    """Run the scanned layer stacks.  Returns (x, aux_loss, cache rows
    (one stack per ``_cache_names`` leaf) | None)."""
    flags = global_flags(cfg)
    akw = _attn_kwargs(cfg)
    impl = "scan" if collect_kv else cfg.attn_impl

    def make_body(moe):
        def body(carry, xs):
            x, aux = carry
            pl, is_global = xs
            wv = layer_window(cfg, is_global)
            if cfg.seq_parallel:
                # residual stream lives (batch x seq)-sharded between blocks;
                # the q/k/v and MLP constraints pull full sequences back in
                # (XLA materializes the AG/RS pair = the usual SP dataflow).
                x = constrain(x, "batch", "seq", None)
            h = rms_norm(x, pl["ln1"], cfg.norm_eps)
            if cfg.attn == "mla":
                res = mla_forward(pl["attn"], cfg, h, positions, impl=impl,
                                  return_cache=collect_kv)
            else:
                res = attn_forward(
                    pl["attn"], h, positions, window=wv,
                    return_kv=collect_kv, impl=impl, **akw,
                )
            o, kv = res if collect_kv else (res, None)
            if collect_kv:
                # pin the collected KV stack so the prefill ys buffer
                # materializes cache-sharded (heads over model when
                # divisible, else sequence).  The barrier stops the
                # constraint propagating INTO the attention loop (which
                # must see the sequence unsharded).
                kv = jax.lax.optimization_barrier(kv)
                heads = ("kv",) if cfg.attn == "mha" else ()
                kv = tuple(constrain(t, "batch", "?seq", *heads, None)
                           for t in kv)
            x = x + o
            h2 = rms_norm(x, pl["ln2"], cfg.norm_eps)
            y, a = _ffn(cfg, pl, h2, moe)
            if moe:
                aux = aux + a
            return (x + y, aux), kv

        return body

    carry, kvs = (x, jnp.float32(0.0)), []
    for name, moe, lo, hi in _segments(cfg):
        carry, kv = jax.lax.scan(
            _maybe_remat(make_body(moe), cfg), carry,
            (params[name], jnp.asarray(flags[lo:hi])),
        )
        kvs.append(kv)
    kvs = kvs[0] if len(kvs) == 1 else jax.tree_util.tree_map(
        lambda *t: jnp.concatenate(t), *kvs)
    return carry[0], carry[1], kvs


def decoder_only_logits(cfg: ModelConfig, params, batch):
    """Training forward.  batch["tokens"]: (b, s) inputs; vlm gets
    batch["patches"]: (b, P, d) prepended.  Returns (logits, aux)."""
    dt = _dtype(cfg)
    tokens = batch["tokens"]
    x = embed(tokens, params["embed"], dt)
    n_prefix = 0
    if cfg.family == "vlm":
        patches = batch["patches"].astype(dt)
        x = jnp.concatenate([patches, x], axis=1)
        n_prefix = patches.shape[1]
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    x, aux, _ = decoder_stack(cfg, params, x, positions)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if n_prefix:
        x = x[:, n_prefix:]  # logits over text positions only
    return unembed(x, _head(cfg, params)), aux


def decoder_only_prefill(cfg: ModelConfig, params, batch, cache_len: int):
    """Prompt pass; returns (last-token logits, cache).

    Cache: {"k","v"}: (L, b, S, g, hd) (S = cache_len), plus lengths.
    When cfg.window and cfg.window_cache, local layers keep only a
    window-sized ring (stored in separate 'lk','lv' stacks) — the optimized
    layout; otherwise all layers use full-length caches.
    """
    dt = _dtype(cfg)
    tokens = batch["tokens"]
    x = embed(tokens, params["embed"], dt)
    if cfg.family == "vlm":
        x = jnp.concatenate([batch["patches"].astype(dt), x], axis=1)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    x, _, kvs = decoder_stack(cfg, params, x, positions, collect_kv=True)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = unembed(x[:, -1:], _head(cfg, params))[:, 0]

    pad = cache_len - s
    if pad < 0:
        raise ValueError("cache_len < prompt length")
    if cfg.attn == "mla":  # latent rows (L, b, s, r | rope)
        cache = {n: jnp.pad(t, ((0, 0), (0, 0), (0, pad), (0, 0)))
                 for n, t in zip(_cache_names(cfg), kvs)}
        return logits, dict(cache, len=jnp.int32(s))
    k_new, v_new = kvs  # (L, b, s, g, hd)
    if cfg.window and cfg.window_cache:
        return logits, _windowed_cache(cfg, k_new, v_new, s, cache_len)
    cache = {"len": jnp.int32(s)}
    if cfg.kv_quant:
        assert not cfg.window, "int8 KV + ring caches not combined"
        # per-(layer, batch, kv-head) symmetric int8 quantization
        ks = jnp.max(jnp.abs(k_new.astype(jnp.float32)), axis=(2, 4)) / 127.0
        vs = jnp.max(jnp.abs(v_new.astype(jnp.float32)), axis=(2, 4)) / 127.0
        ks = jnp.maximum(ks, 1e-6)
        vs = jnp.maximum(vs, 1e-6)
        k_new = jnp.clip(
            jnp.round(k_new / ks[:, :, None, :, None]), -127, 127
        ).astype(jnp.int8)
        v_new = jnp.clip(
            jnp.round(v_new / vs[:, :, None, :, None]), -127, 127
        ).astype(jnp.int8)
        cache["ks"], cache["vs"] = ks, vs
    cache["k"] = jnp.pad(k_new, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
    cache["v"] = jnp.pad(v_new, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
    return logits, cache


def _windowed_cache(cfg, k_new, v_new, s: int, cache_len: int):
    """Grouped cache for sliding-window archs (gemma3 5:1): global layers
    keep the full sequence, local layers keep a W-slot RING holding the last
    W tokens (ring slot of logical position p is p % W) — 26 full caches
    collapse to 4 full + 22 windows (the §Perf memory win at 500k).
    """
    W = cfg.window
    flags = global_flags(cfg)
    gidx = tuple(int(i) for i in np.nonzero(flags)[0])
    lidx = tuple(int(i) for i in np.nonzero(~flags)[0])
    pad = cache_len - s
    gk = jnp.pad(k_new[jnp.asarray(gidx)],
                 ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
    gv = jnp.pad(v_new[jnp.asarray(gidx)],
                 ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
    lk = k_new[jnp.asarray(lidx)][:, :, max(0, s - W):]
    lv = v_new[jnp.asarray(lidx)][:, :, max(0, s - W):]
    if s < W:  # short prompts: slots 0..s-1 are just positions 0..s-1
        lk = jnp.pad(lk, ((0, 0), (0, 0), (0, W - s), (0, 0), (0, 0)))
        lv = jnp.pad(lv, ((0, 0), (0, 0), (0, W - s), (0, 0), (0, 0)))
    else:      # last W tokens land at slots (s-W+i) % W: a roll by s % W
        lk = jnp.roll(lk, s % W, axis=2)
        lv = jnp.roll(lv, s % W, axis=2)
    return {"gk": gk, "gv": gv, "lk": lk, "lv": lv, "len": jnp.int32(s)}


def _windowed_decode(cfg: ModelConfig, params, cache, tokens, pos):
    """Decode with grouped window caches: a statically-unrolled layer loop
    (decode graphs are one token — 26 unrolled layers stay small), local
    layers on the ring path, global layers on the linear path."""
    dt = _dtype(cfg)
    x = embed(tokens, params["embed"], dt)
    akw = _attn_kwargs(cfg)
    flags = global_flags(cfg)
    gk, gv, lk, lv = cache["gk"], cache["gv"], cache["lk"], cache["lv"]
    gi = li = 0
    new_g, new_l = [], []
    for i in range(cfg.n_layers):
        pl = jax.tree_util.tree_map(lambda a: a[i], params["layers"])
        h = rms_norm(x, pl["ln1"], cfg.norm_eps)
        if flags[i]:
            o, nc = attn_decode(
                pl["attn"], h, {"k": gk[gi], "v": gv[gi]}, pos, **akw
            )
            new_g.append(nc)
            gi += 1
        else:
            o, nc = attn_decode(
                pl["attn"], h, {"k": lk[li], "v": lv[li]}, pos, ring=True,
                **akw,
            )
            new_l.append(nc)
            li += 1
        x = x + o
        h2 = rms_norm(x, pl["ln2"], cfg.norm_eps)
        x = x + gated_mlp(h2, pl["mlp"]["wi"], pl["mlp"]["wo"], cfg.act)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = unembed(x[:, 0], _head(cfg, params))
    out = {
        "gk": jnp.stack([c["k"] for c in new_g]),
        "gv": jnp.stack([c["v"] for c in new_g]),
        "lk": jnp.stack([c["k"] for c in new_l]),
        "lv": jnp.stack([c["v"] for c in new_l]),
        "len": cache["len"] + 1,
    }
    return logits, out


def _cached_stack(cfg: ModelConfig, params, cache, x, pos, *, pages=None,
                  page_size=None, valid_len=None, scratch=None):
    """Scanned layer stacks over a cache: x is (b, s, d) with s >= 1 new
    tokens starting at position ``pos`` (scalar, or per-row ``(b,)`` for
    the continuous-batching slot layout).  Shared by the one-token decode
    step and the chunked prefill-extend path.

    Without ``pages`` the cache is linear, (L, b, S, ...) per leaf (int8
    K/V with their "ks"/"vs" scales too).  With ``pages`` it is the PAGED
    pool (DESIGN.md §13), (L, P, page_size, ...) per leaf, one pooled
    buffer of physical pages shared by every slot, and ``pages`` (b, n_pg)
    maps each row's logical positions to them; ``valid_len``/``scratch``
    are the padded write barrier.  The paged body mirrors the linear one
    operation-for-operation (same norms, same residual order, same
    attention math on the gathered rows), so both layouts produce
    bitwise-identical activations.  Returns (x after the final norm,
    {leaf: new (L, ...) stack} for ``_cache_names``)."""
    names = _cache_names(cfg)
    paged = pages is not None
    if paged:
        assert "ks" not in cache, "paged pools are fp-only"
    carried = names + (("ks", "vs") if "ks" in cache else ())
    flags = global_flags(cfg)
    akw = _attn_kwargs(cfg)

    def attend(pa, h, lc, wv):
        if cfg.attn == "mla" and paged:
            o, c, pe = mla_decode_paged(
                pa, cfg, h, lc["c_kv"], lc["k_pe"], pages, pos,
                page_size=page_size, valid_len=valid_len, scratch=scratch)
            return o, {"c_kv": c, "k_pe": pe}
        if cfg.attn == "mla":
            return mla_decode(pa, cfg, h, lc, pos)
        if paged:
            o, kc, vc = attn_decode_paged(
                pa, h, lc["k"], lc["v"], pages, pos, page_size=page_size,
                window=wv, valid_len=valid_len, scratch=scratch, **akw,
            )
            return o, {"k": kc, "v": vc}
        return attn_decode(pa, h, lc, pos, window=wv, **akw)

    def block(pl, moe, x, is_global, lc):
        wv = layer_window(cfg, is_global)
        h = rms_norm(x, pl["ln1"], cfg.norm_eps)
        o, nc = attend(pl["attn"], h, lc, wv)
        x = x + o
        h2 = rms_norm(x, pl["ln2"], cfg.norm_eps)
        y, _ = _ffn(cfg, pl, h2, moe)
        return x + y, {n: nc[n] for n in names}

    segs = _segments(cfg)
    lc = {n: cache[n] for n in carried}
    if len(segs) == 1:
        # one kind of block: its weights and cache leaves ride the scan.
        # The carried form below would serve here too, but on a TPU v5e it
        # made Qwen1.5-MoE's paged decode 1.5% and its extend 17% slower.
        name, moe, _, _ = segs[0]
        x, new = jax.lax.scan(
            lambda x, xs: block(xs[0], moe, x, *xs[1:]), x,
            (params[name], jnp.asarray(flags), lc))
    else:
        # leading dense layers, then expert layers: a scan per kind, each
        # reading and writing its own layers of the whole cache leaves in
        # its carry (slicing the leaves into per-kind scan inputs and
        # concatenating the outputs would hold two more pool-sized buffers)
        def make_body(moe):
            def body(carry, xs):
                x, leaves = carry
                pl, i, is_global = xs
                x, nc = block(pl, moe, x, is_global, {
                    n: jax.lax.dynamic_index_in_dim(leaves[n], i, 0, False)
                    for n in carried})
                return (x, {n: jax.lax.dynamic_update_index_in_dim(
                    leaves[n], nc[n], i, 0) if n in nc else leaves[n]
                    for n in carried}), None

            return body

        for name, moe, lo, hi in segs:
            (x, lc), _ = jax.lax.scan(make_body(moe), (x, lc), (
                params[name], jnp.arange(lo, hi, dtype=jnp.int32),
                jnp.asarray(flags[lo:hi])))
        new = {n: lc[n] for n in names}
    return rms_norm(x, params["final_norm"], cfg.norm_eps), new


def decoder_only_decode(cfg: ModelConfig, params, cache, tokens, pos,
                        pages=None, page_size=None):
    """One decode step.  tokens: (b, 1); pos: scalar position of the new
    token, or ``(b,)`` per-row positions (continuous-batching slots).
    With ``pages``/``page_size`` the cache is a paged pool and reads/
    writes route through the page-table indirection (DESIGN.md §13)."""
    if "lk" in cache:
        return _windowed_decode(cfg, params, cache, tokens, pos)
    dt = _dtype(cfg)
    x = embed(tokens, params["embed"], dt)
    x, new = _cached_stack(cfg, params, cache, x, pos, pages=pages,
                           page_size=page_size)
    logits = unembed(x[:, 0], _head(cfg, params))
    out = dict(cache, **new)
    out["len"] = cache["len"] + 1
    return logits, out


def decoder_only_extend(cfg: ModelConfig, params, cache, tokens, pos,
                        logit_index=None, pages=None, page_size=None,
                        valid_len=None, scratch=None):
    """Chunked prefill-extend: append a CHUNK of tokens to a linear cache.

    tokens: (b, C) land at positions pos..pos+C-1 (pos scalar or per-row
    ``(b,)``) with causal attention inside the chunk and full attention
    over the cache prefix.  Returns (logits (b, C, V) over ALL C
    positions, updated cache); with ``logit_index`` (a scalar chunk
    position, may be traced) only that position is unembedded —
    (b, 1, V) — which is what the serve engine's admission loop reads
    (unembedding a whole chunk against a real vocab is the dominant
    prefill cost, and only the last REAL prompt position's row is ever
    used; DESIGN.md §12).  Ring (grouped sliding-window) caches are not
    supported; serve lowers such archs to the masked linear-cache layout.
    With ``pages``/``page_size`` the chunk lands in a paged pool through
    the page-table indirection instead (DESIGN.md §13); ``valid_len``/
    ``scratch`` (paged only) route per-row pad tokens past ``valid_len``
    into a throwaway scratch page instead of through the table — the
    padded write barrier for bucketed prefill over the pool.
    """
    if "lk" in cache:
        raise NotImplementedError(
            "extend over grouped ring caches is unsupported; build the "
            "cache with window_cache=False (full-length + window mask)"
        )
    if pages is None:
        assert valid_len is None and scratch is None, \
            "the padded write barrier is a paged-pool construct"
    dt = _dtype(cfg)
    x = embed(tokens, params["embed"], dt)
    x, new = _cached_stack(cfg, params, cache, x, pos, pages=pages,
                           page_size=page_size, valid_len=valid_len,
                           scratch=scratch)
    if logit_index is not None:
        x = jax.lax.dynamic_index_in_dim(x, logit_index, axis=1,
                                         keepdims=True)
    logits = unembed(x, _head(cfg, params))
    out = dict(cache, **new)
    out["len"] = cache["len"] + tokens.shape[1]
    return logits, out


# ------------------------------------------------------------------ encdec
def encode(cfg: ModelConfig, params, frames):
    """frames: (b, F, d) stub embeddings -> encoder states (b, F, d)."""
    dt = _dtype(cfg)
    x = frames.astype(dt)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    akw = _attn_kwargs(cfg)

    def body(x, pl):
        h = rms_norm(x, pl["ln1"], cfg.norm_eps)
        x = x + attn_forward(
            pl["attn"], h, positions, causal=False, impl=cfg.attn_impl, **akw
        )
        h2 = rms_norm(x, pl["ln2"], cfg.norm_eps)
        return x + gated_mlp(h2, pl["mlp"]["wi"], pl["mlp"]["wo"], cfg.act), None

    x, _ = jax.lax.scan(_maybe_remat(body, cfg), x, params["enc_layers"])
    return rms_norm(x, params["enc_norm"], cfg.norm_eps)


def _dec_stack(cfg, params, x, positions, enc, *, collect_kv=False):
    akw = _attn_kwargs(cfg)

    def body(x, pl):
        h = rms_norm(x, pl["ln1"], cfg.norm_eps)
        res = attn_forward(
            pl["self_attn"], h, positions, return_kv=collect_kv,
            impl="scan" if collect_kv else cfg.attn_impl, **akw,
        )
        o, kv = res if collect_kv else (res, None)
        if collect_kv:
            kv = jax.lax.optimization_barrier(kv)
            kv = tuple(constrain(t, "batch", "?seq", "kv", None) for t in kv)
        x = x + o
        h2 = rms_norm(x, pl["ln2"], cfg.norm_eps)
        x = x + attn_forward(
            pl["cross_attn"], h2, positions, enc=enc, impl=cfg.attn_impl, **akw
        )
        h3 = rms_norm(x, pl["ln3"], cfg.norm_eps)
        return x + gated_mlp(h3, pl["mlp"]["wi"], pl["mlp"]["wo"], cfg.act), kv

    return jax.lax.scan(_maybe_remat(body, cfg), x, params["dec_layers"])


def encdec_logits(cfg: ModelConfig, params, batch):
    dt = _dtype(cfg)
    enc = encode(cfg, params, batch["frames"])
    tokens = batch["tokens"]
    x = embed(tokens, params["embed"], dt)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    x, _ = _dec_stack(cfg, params, x, positions, enc)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return unembed(x, params["embed"]), jnp.float32(0.0)


def encdec_prefill(cfg: ModelConfig, params, batch, cache_len: int):
    dt = _dtype(cfg)
    enc = encode(cfg, params, batch["frames"])
    tokens = batch["tokens"]
    x = embed(tokens, params["embed"], dt)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    x, kvs = _dec_stack(cfg, params, x, positions, enc, collect_kv=True)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = unembed(x[:, -1:], params["embed"])[:, 0]
    k_new, v_new = kvs
    pad = cache_len - s
    kc = jnp.pad(k_new, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
    vc = jnp.pad(v_new, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
    return logits, {"k": kc, "v": vc, "enc": enc, "len": jnp.int32(s)}


def encdec_decode(cfg: ModelConfig, params, cache, tokens, pos):
    dt = _dtype(cfg)
    x = embed(tokens, params["embed"], dt)
    enc = cache["enc"]
    akw = _attn_kwargs(cfg)

    def body(x, xs):
        pl, kc, vc = xs
        h = rms_norm(x, pl["ln1"], cfg.norm_eps)
        o, nc = attn_decode(pl["self_attn"], h, {"k": kc, "v": vc}, pos, **akw)
        x = x + o
        h2 = rms_norm(x, pl["ln2"], cfg.norm_eps)
        o2, _ = attn_decode(pl["cross_attn"], h2, None, pos, enc=enc, **akw)
        x = x + o2
        h3 = rms_norm(x, pl["ln3"], cfg.norm_eps)
        return x + gated_mlp(h3, pl["mlp"]["wi"], pl["mlp"]["wo"], cfg.act), (
            nc["k"],
            nc["v"],
        )

    x, (kc, vc) = jax.lax.scan(body, x, (params["dec_layers"], cache["k"], cache["v"]))
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = unembed(x[:, 0], params["embed"])
    return logits, {"k": kc, "v": vc, "enc": enc, "len": cache["len"] + 1}
