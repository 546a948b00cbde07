"""Multi-head latent attention (DeepSeek-V2/V3, no q compression).

Per token the cache holds one latent row ``c_kv`` (``kv_lora_rank``
wide, after its RMSNorm) and one rope key ``k_pe`` (``qk_rope_dim``)
shared by every head; no per-head K or V is ever stored.

    q              = x · W_q                       (H, nope + rope)
    [c_kv ; k_pe]  = x · W_kva,  c_kv = RMSNorm(c_kv),  rope on q_pe, k_pe
    [k_nope ; v]   = c_kv · W_kvb                  (H, nope + v)
    score          = (q_nope · k_nope + q_pe · k_pe) / sqrt(nope + rope)
    out            = (softmax(score) · v) · W_o

Two forms compute it over the same latent rows:

* expanded (``mla.expand``: training, prefill, extend): K and V of every
  cached position are expanded per head through ``W_kvb``;
* absorbed (``mla.absorbed``: the one-token decode step): ``W_kvb``'s key
  half is folded into the query, ``q_lat = q_nope · W_UKᵀ`` (``kv_lora_rank``
  per head), and its value half applied after the weighted sum:
  ``score = q_lat · c_kv + q_pe · k_pe``, ``o = (softmax · c_kv) · W_UV``.
  Each cached position is then read once, 2·(rank + rope) bytes, for all
  heads.

Cached paths take a linear cache ``{"c_kv": (b, S, r), "k_pe": (b, S,
rope)}`` or, through a page table, the paged pool ``(P, page_size, ...)``
with the padded write barrier of ``attention.attn_decode_paged``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.dist.act_sharding import constrain

from .attention import NEG_INF, flash_attention, page_slots
from .layers import init_linear, init_norm, rms_norm, rope

__all__ = ["init_mla", "mla_forward", "mla_decode", "mla_decode_paged"]


def init_mla(key, cfg, dtype):
    d, H, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "wq": init_linear(k1, (d, H, dn + dr), dtype),
        "wkv_a": init_linear(k2, (d, r + dr), dtype),
        "kv_norm": init_norm((r,), dtype),
        "wkv_b": init_linear(k3, (r, H, dn + dv), dtype),
        "wo": init_linear(k4, (H, dv, d), dtype),
    }


def _project(p, cfg, x, positions):
    """x: (b, s, d) at ``positions`` (b, s) -> q_nope (b, s, H, nope),
    roped q_pe (b, s, H, rope), normed c_kv (b, s, r), roped k_pe (b, s,
    rope)."""
    dt, r, dn = x.dtype, cfg.kv_lora_rank, cfg.qk_nope_dim
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    q = constrain(q, "?batch_plus", None, "heads", None)
    q_pe = rope(q[..., dn:], positions, cfg.rope_theta)
    kv = jnp.einsum("bsd,dk->bsk", x, p["wkv_a"].astype(dt))
    c_kv = rms_norm(kv[..., :r], p["kv_norm"], cfg.norm_eps)
    k_pe = rope(kv[..., None, r:], positions, cfg.rope_theta)[..., 0, :]
    return q[..., :dn], q_pe, c_kv, k_pe


def _attend(p, cfg, q_nope, q_pe, c_rows, pe_rows, qpos, absorbed: bool):
    """Attention of queries at positions ``qpos`` ((b | 1, s)) over the
    latent rows ``c_rows`` (b, S, r) and ``pe_rows`` (b, S, rope) of
    positions 0..S-1, causally; -> (b, s, d)."""
    dt, dn = q_nope.dtype, cfg.qk_nope_dim
    scale = (dn + cfg.qk_rope_dim) ** -0.5
    c_rows, pe_rows = c_rows.astype(dt), pe_rows.astype(dt)
    wkv_b = p["wkv_b"].astype(dt)
    mask = jnp.arange(c_rows.shape[1])[None, None, :] <= qpos[..., None]
    rope_s = jnp.einsum("bshe,bke->bhsk", q_pe, pe_rows,
                        preferred_element_type=jnp.float32)
    if absorbed:
        with jax.named_scope("mla.absorbed"):
            q_lat = jnp.einsum("bshn,rhn->bshr", q_nope, wkv_b[..., :dn])
            s = jnp.einsum("bshr,bkr->bhsk", q_lat, c_rows,
                           preferred_element_type=jnp.float32) + rope_s
            s = jnp.where(mask[:, None], s * scale, NEG_INF)
            pr = jax.nn.softmax(s, axis=-1).astype(dt)
            o_lat = jnp.einsum("bhsk,bkr->bshr", pr, c_rows,
                               preferred_element_type=jnp.float32)
            o = jnp.einsum("bshr,rhv->bshv", o_lat.astype(dt), wkv_b[..., dn:])
    else:
        with jax.named_scope("mla.expand"):
            kv = jnp.einsum("bkr,rhn->bkhn", c_rows, wkv_b)
            s = jnp.einsum("bshn,bkhn->bhsk", q_nope, kv[..., :dn],
                           preferred_element_type=jnp.float32) + rope_s
            s = jnp.where(mask[:, None], s * scale, NEG_INF)
            pr = jax.nn.softmax(s, axis=-1).astype(dt)
            o = jnp.einsum("bhsk,bkhv->bshv", pr, kv[..., dn:],
                           preferred_element_type=jnp.float32).astype(dt)
    out = jnp.einsum("bshv,hvd->bsd", o, p["wo"].astype(dt))
    return constrain(out, "batch", None, None)


def mla_forward(p, cfg, x, positions, *, impl="vjp", return_cache=False):
    """Training / prefill over a whole sequence, expanded form through the
    chunked flash attention (v zero-padded to the q.k head width, which
    keeps its 1/sqrt(nope + rope) scale).  With ``return_cache`` also the
    cache rows ``(c_kv, k_pe)``."""
    dt, dn, dv = x.dtype, cfg.qk_nope_dim, cfg.v_head_dim
    b, s, _ = x.shape
    q_nope, q_pe, c_kv, k_pe = _project(p, cfg, x, positions)
    with jax.named_scope("mla.expand"):
        kv = jnp.einsum("bsr,rhn->bshn", c_kv, p["wkv_b"].astype(dt))
        H = kv.shape[2]
        q = jnp.concatenate([q_nope, q_pe], -1)
        k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
            k_pe[:, :, None], (b, s, H, cfg.qk_rope_dim))], -1)
        v = jnp.pad(kv[..., dn:], ((0, 0),) * 3 + ((0, q.shape[-1] - dv),))
        o = flash_attention(q, k, v, causal=True, impl=impl)[..., :dv]
    out = jnp.einsum("bshv,hvd->bsd", o, p["wo"].astype(dt))
    out = constrain(out, "batch", None, None)
    return (out, (c_kv, k_pe)) if return_cache else out


def _positions(pos, b, s):
    pos_arr = jnp.asarray(pos)
    return jnp.broadcast_to(
        jnp.reshape(pos_arr, (-1, 1)) + jnp.arange(s)[None, :], (b, s))


def mla_decode(p, cfg, x, cache, pos):
    """Cached attention over a linear latent cache: x (b, s, d) new tokens
    at ``pos`` (scalar, or per-row (b,)) write their rows, then attend,
    absorbed for one token, expanded for a chunk.  Returns (out, new
    cache)."""
    b, s, _ = x.shape
    positions = _positions(pos, b, s)
    q_nope, q_pe, c_new, pe_new = _project(p, cfg, x, positions)
    pos_arr = jnp.asarray(pos)
    if pos_arr.ndim:
        put = jax.vmap(lambda c, u, st: jax.lax.dynamic_update_slice_in_dim(
            c, u, st, axis=0))
        starts = pos_arr.astype(jnp.int32)
    else:
        put = lambda c, u, st: jax.lax.dynamic_update_slice_in_dim(  # noqa: E731
            c, u, st, axis=1)
        starts = pos_arr
    new = {n: put(cache[n], u.astype(cache[n].dtype), starts)
           for n, u in (("c_kv", c_new), ("k_pe", pe_new))}
    out = _attend(p, cfg, q_nope, q_pe, new["c_kv"], new["k_pe"], positions,
                  absorbed=s == 1)
    return out, new


def mla_decode_paged(p, cfg, x, c_pool, pe_pool, pages, pos, *, page_size,
                     valid_len=None, scratch=None):
    """Cached attention through the page table (``attn_decode_paged``'s
    layout and write barrier): the new tokens' latent rows scatter to
    their pages, the rows of each table row gather back, and the queries
    attend, absorbed for one token, expanded for a chunk.  Returns (out,
    c_pool, pe_pool)."""
    b, s, _ = x.shape
    positions = _positions(pos, b, s)
    q_nope, q_pe, c_new, pe_new = _project(p, cfg, x, positions)
    pid, off = page_slots(pages, positions, page_size, valid_len, scratch)
    c_pool = c_pool.at[pid, off].set(c_new.astype(c_pool.dtype))
    pe_pool = pe_pool.at[pid, off].set(pe_new.astype(pe_pool.dtype))
    rows = pages.shape[1] * page_size
    c_rows = c_pool[pages].reshape(b, rows, c_pool.shape[-1])
    pe_rows = pe_pool[pages].reshape(b, rows, pe_pool.shape[-1])
    out = _attend(p, cfg, q_nope, q_pe, c_rows, pe_rows, positions,
                  absorbed=s == 1)
    return out, c_pool, pe_pool
