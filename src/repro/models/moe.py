"""Mixture-of-Experts FFN with top-k routing, capacity, and EP sharding.

The routed experts run in one of two forms, chosen by ``dispatch_form``
from the call's token width and config alone:

- **buffer** (the one-token decode, training's ``capacity_factor`` 1.25,
  and every call under an active ``use_mesh``): dispatch is PER-ROW
  (each batch row routes its own tokens): the (token, expert) assignments
  are argsorted WITHIN a row, ranked, dropped beyond the per-row capacity
  C, and scattered into per-row expert buffers (b, E, C, d) that two
  einsums multiply by all E experts.  Because rows never mix, the whole
  dispatch is local to the data shard that owns the row — no
  cross-device sort networks.  The only collectives left are the genuine
  expert-parallel ones at the einsum boundary: buf is batch-sharded,
  expert weights are experts- (moonshot, E%16==0) or expert-ff- (qwen,
  60e) sharded over 'model', and XLA materializes the all-to-all / psum
  pair exactly there.
- **grouped** (more than one token at a dropless capacity, C >= s, with
  no mesh): a token's top-k experts are distinct, so no expert receives
  more than s assignments of a row and none is dropped; the assignments
  are sorted by expert, gathered into one (b*s*K, d) operand and
  multiplied by each expert's weights only over the rows routed to it
  (``lax.ragged_dot``), then weighted and added back to their tokens.
  The same mathematics as the buffer form at that capacity, over
  E*C / (s*K) times fewer expert rows: at least E/K, about 11 for 64
  experts top-6 at ``capacity_factor`` 11.

Two routers share the dispatch: ``softmax`` (top-k of the softmax,
renormalised; qwen2-moe) and ``sigmoid_bias`` (DeepSeek-V3 ``noaux_tc``
with one group: the top-k of sigmoid scores plus a correction bias
choose the experts, the unbiased scores of those experts, normalised to
sum 1 and times ``routed_scale``, weight them).

Shared experts (qwen2-moe: 4, moonlight: 2) run densely over all tokens,
ungated.  Aux load-balance loss (Switch-style) is returned for the trainer.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.dist.act_sharding import constrain, current_mesh

from .layers import init_linear

__all__ = ["init_moe", "route", "capacity", "dispatch_form", "moe_forward"]


def init_moe(key, cfg, dtype):
    d, E, f = cfg.d_model, cfg.n_experts, cfg.expert_dff
    ks = jax.random.split(key, 4)
    p = {
        "router": init_linear(ks[0], (d, E), jnp.float32),
        "wi": init_linear(ks[1], (E, d, 2, f), dtype),
        "wo": init_linear(ks[2], (E, f, d), dtype),
    }
    if cfg.n_shared:
        p["shared_wi"] = init_linear(ks[3], (d, 2, cfg.n_shared * f), dtype)
        p["shared_wo"] = init_linear(ks[0], (cfg.n_shared * f, d), dtype)
    if cfg.router == "sigmoid_bias":
        # e_score_correction_bias: chooses experts, never weights them
        p["router_bias"] = init_linear(jax.random.fold_in(key, 4), (E,),
                                       jnp.float32)
    return p


def route(p, cfg, x):
    """x: (b, s, d) -> (gates (b, s, K) f32, expert ids (b, s, K), the
    per-expert probabilities (b, s, E) the aux loss balances)."""
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32), p["router"])
    if cfg.router == "sigmoid_bias":
        scores = jax.nn.sigmoid(logits)
        _, idx = jax.lax.top_k(scores + p["router_bias"], cfg.top_k)
        gate_vals = jnp.take_along_axis(scores, idx, axis=-1)
        gate_vals = gate_vals / (jnp.sum(gate_vals, axis=-1, keepdims=True)
                                 + 1e-20) * cfg.routed_scale
        return gate_vals, idx, scores / jnp.sum(scores, -1, keepdims=True)
    probs = jax.nn.softmax(logits, axis=-1)  # (b, s, E)
    gate_vals, idx = jax.lax.top_k(probs, cfg.top_k)  # (b, s, K)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)
    return gate_vals, idx, probs


def _dispatch_row(x_row, idx_row, gates_row, E, C, K):
    """One row: x (s, d), idx (s, K), gates (s, K) -> buf (E, C, d),
    plus (dest, tok, weight) for the return scatter."""
    s, d = x_row.shape
    eflat = idx_row.reshape(-1)  # (s*K,)
    order = jnp.argsort(eflat, stable=True)
    sorted_e = eflat[order]
    seg_start = jnp.searchsorted(sorted_e, jnp.arange(E), side="left")
    rank = jnp.arange(s * K) - seg_start[sorted_e]
    keep = rank < C
    tok = order // K
    dest = jnp.where(keep, sorted_e * C + rank, E * C)  # overflow -> dump row
    buf = jnp.zeros((E * C + 1, d), x_row.dtype).at[dest].set(x_row[tok])
    w = (gates_row.reshape(-1)[order] * keep).astype(x_row.dtype)
    return buf[: E * C].reshape(E, C, d), dest, tok, w


def capacity(cfg, s: int) -> int:
    """Per-row expert capacity C of the buffer form at ``s`` tokens."""
    return max(1, int(s * cfg.top_k / cfg.n_experts * cfg.capacity_factor))


def dispatch_form(cfg, s: int, mesh=None) -> str:
    """Which form ``moe_forward`` runs for rows of ``s`` tokens:
    ``"grouped"`` where the capacity is dropless (C >= s: a token's top-k
    experts are distinct, so no expert can receive more than s of a row's
    assignments) for more than one token and no ``mesh`` (the active
    ``use_mesh``) is given, ``"buffer"`` otherwise."""
    if s > 1 and capacity(cfg, s) >= s and mesh is None:
        return "grouped"
    return "buffer"


def _routed_buffer(p, cfg, x, gate_vals, idx, C, act):
    """The buffer form: every row's (E, C, d) expert buffers through two
    einsums over all E experts, tokens past C dropped."""
    dt = x.dtype
    b, s, d = x.shape
    E, K = cfg.n_experts, cfg.top_k
    buf, dest, tok, w = jax.vmap(
        lambda xr, ir, gr: _dispatch_row(xr, ir, gr, E, C, K)
    )(x, idx, gate_vals)
    # buf: (b, E, C, d) batch-sharded; expert weights model-sharded -> the
    # contraction boundary below is where EP collectives materialize.
    buf = constrain(buf, "batch", "experts", None, None)

    h = jnp.einsum("becd,edgf->becgf", buf, p["wi"].astype(dt))
    h = constrain(h, "batch", "experts", None, None, "ff")
    h = act(h[..., 0, :]) * h[..., 1, :]
    out_buf = jnp.einsum("becf,efd->becd", h, p["wo"].astype(dt))
    out_buf = constrain(out_buf, "batch", "experts", None, None)
    out_flat = out_buf.reshape(b, E * C, d)

    def gather_row(ob_row, dest_row, tok_row, w_row):
        padded = jnp.concatenate(
            [ob_row, jnp.zeros((1, d), dt)], axis=0
        )[dest_row]  # (s*K, d)
        y = jnp.zeros((s, d), dt).at[tok_row].add(padded * w_row[:, None])
        return y

    return jax.vmap(gather_row)(out_flat, dest, tok, w)


def _routed_grouped(p, cfg, x, gate_vals, idx, act):
    """The grouped form: the (token, expert) assignments of all b*s
    tokens sorted by expert, and each expert's weights multiplied only by
    the rows routed to it (``lax.ragged_dot``), then weighted by the gates
    and added back to their tokens."""
    dt = x.dtype
    b, s, d = x.shape
    E, K, f = cfg.n_experts, cfg.top_k, cfg.expert_dff
    eflat = idx.reshape(-1)  # (b*s*K,)
    order = jnp.argsort(eflat, stable=True)
    tok = order // K
    sizes = jnp.bincount(eflat, length=E).astype(jnp.int32)
    xs = x.reshape(b * s, d)[tok]  # (b*s*K, d) in expert order
    h = jax.lax.ragged_dot(xs, p["wi"].astype(dt).reshape(E, d, 2 * f),
                           sizes)
    h = act(h[:, :f]) * h[:, f:]
    out = jax.lax.ragged_dot(h, p["wo"].astype(dt), sizes)
    w = gate_vals.reshape(-1)[order].astype(dt)
    y = jnp.zeros((b * s, d), dt).at[tok].add(out * w[:, None])
    return y.reshape(b, s, d)


def moe_forward(p, cfg, x):
    """x: (b, s, d) -> (y: (b, s, d), aux_loss: scalar f32)."""
    dt, s = x.dtype, x.shape[1]
    E, K = cfg.n_experts, cfg.top_k

    gate_vals, idx, probs = route(p, cfg, x)

    # Switch-style aux loss (global): E * sum_e fraction_e * mean_prob_e
    onehot = jax.nn.one_hot(idx, E, dtype=jnp.float32)  # (b, s, K, E)
    frac = jnp.mean(jnp.sum(onehot, axis=2), axis=(0, 1))
    aux = E * jnp.sum(frac / K * jnp.mean(probs, axis=(0, 1)))

    act = jax.nn.gelu if cfg.act == "geglu" else jax.nn.silu
    if dispatch_form(cfg, s, current_mesh()) == "grouped":
        with jax.named_scope("moe.grouped"):
            y = _routed_grouped(p, cfg, x, gate_vals, idx, act)
    else:
        with jax.named_scope("moe.buffer"):
            y = _routed_buffer(p, cfg, x, gate_vals, idx, capacity(cfg, s),
                               act)

    if cfg.n_shared:
        hs = jnp.einsum("bsd,dgf->bsgf", x, p["shared_wi"].astype(dt))
        hs = constrain(hs, "batch", None, None, "ff")
        hs = act(hs[..., 0, :]) * hs[..., 1, :]
        y = y + jnp.einsum("bsf,fd->bsd", hs, p["shared_wo"].astype(dt))

    return constrain(y, "batch", None, None), aux
