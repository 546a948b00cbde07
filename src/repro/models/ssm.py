"""Mamba2 / SSD (state-space duality) block, pure JAX.

Training/prefill uses the chunked SSD algorithm (arXiv:2405.21060): quadratic
attention-like computation inside fixed-size chunks (MXU-friendly einsums)
plus a linear inter-chunk state scan.  Decode is the O(1)-per-token SSM
recurrence over a (heads, dstate, headdim) state plus a depthwise-conv ring.

All einsums accumulate in f32 (preferred_element_type) with bf16 operands.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.dist.act_sharding import constrain

from .layers import init_linear, rms_norm

__all__ = ["init_mamba2", "mamba2_forward", "mamba2_decode", "init_ssm_state"]


def _dims(cfg):
    d_in = cfg.d_inner
    h = cfg.ssm_heads
    p = cfg.ssm_headdim
    ds = cfg.ssm_state
    conv_ch = d_in + 2 * ds  # x, B, C share the conv (n_groups = 1)
    return d_in, h, p, ds, conv_ch


def init_mamba2(key, cfg, dtype):
    d = cfg.d_model
    d_in, h, p, ds, conv_ch = _dims(cfg)
    ks = jax.random.split(key, 4)
    proj_out = 2 * d_in + 2 * ds + h  # z, x, B, C, dt
    return {
        "in_proj": init_linear(ks[0], (d, proj_out), dtype),
        "conv_w": init_linear(ks[1], (cfg.ssm_conv, conv_ch), dtype, scale=0.1),
        "conv_b": jnp.zeros((conv_ch,), dtype),
        "A_log": jnp.log(
            jax.random.uniform(ks[2], (h,), jnp.float32, 1.0, 16.0)
        ).astype(jnp.float32),
        "D": jnp.ones((h,), jnp.float32),
        "dt_bias": jnp.log(
            jnp.expm1(
                jnp.exp(
                    jax.random.uniform(
                        ks[3], (h,), jnp.float32,
                        jnp.log(1e-3), jnp.log(1e-1),
                    )
                )
            )
        ).astype(jnp.float32),
        "norm": jnp.zeros((d_in,), dtype),
        "out_proj": init_linear(ks[1], (d_in, d), dtype),
    }


def _split_proj(cfg, zxbcdt):
    d_in, h, p, ds, _ = _dims(cfg)
    z, x, B, C, dt = jnp.split(
        zxbcdt, [d_in, 2 * d_in, 2 * d_in + ds, 2 * d_in + 2 * ds], axis=-1
    )
    return z, x, B, C, dt


def _causal_depthwise_conv(x, w, b):
    """x: (b, s, c); w: (W, c); left-padded causal depthwise conv + silu."""
    W = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
    out = sum(
        xp[:, i : i + x.shape[1], :] * w[i][None, None, :] for i in range(W)
    )
    return jax.nn.silu(out + b[None, None, :])


def mamba2_forward(params, cfg, u, *, initial_state=None):
    """u: (b, s, d) -> (b, s, d).  s must be a multiple of cfg.ssm_chunk."""
    dt_ = u.dtype
    b, s, d = u.shape
    d_in, h, p, ds, conv_ch = _dims(cfg)
    Q = min(cfg.ssm_chunk, s)
    assert s % Q == 0, "sequence must be a multiple of ssm_chunk"
    nc = s // Q

    zxbcdt = jnp.einsum("bsd,de->bse", u, params["in_proj"].astype(dt_))
    z, x, B, C, dtraw = _split_proj(cfg, zxbcdt)
    xBC_raw = jnp.concatenate([x, B, C], axis=-1)
    xBC = _causal_depthwise_conv(
        xBC_raw, params["conv_w"].astype(dt_), params["conv_b"].astype(dt_)
    )
    x, B, C = jnp.split(xBC, [d_in, d_in + ds], axis=-1)

    x = x.reshape(b, s, h, p).astype(jnp.float32)
    x = constrain(x, "batch", None, "heads", None)
    B = B.astype(jnp.float32)  # (b, s, ds) single group
    C = C.astype(jnp.float32)
    dt = jax.nn.softplus(
        dtraw.astype(jnp.float32) + params["dt_bias"][None, None, :]
    )  # (b, s, h)
    A = -jnp.exp(params["A_log"])  # (h,)
    dA = dt * A[None, None, :]  # (b, s, h), negative

    # ---- chunked SSD ----
    xc = x.reshape(b, nc, Q, h, p)
    Bc = B.reshape(b, nc, Q, ds)
    Cc = C.reshape(b, nc, Q, ds)
    dtc = dt.reshape(b, nc, Q, h)
    dAc = dA.reshape(b, nc, Q, h)
    cum = jnp.cumsum(dAc, axis=2)  # (b, nc, Q, h) within-chunk cumulative decay

    # Intra-chunk ("diagonal") term: attention-like with decay mask.
    # L[i, j] = exp(cum_i - cum_j) for i >= j else 0.
    rel = cum[:, :, :, None, :] - cum[:, :, None, :, :]  # (b, nc, Q, Q, h)
    tri = jnp.tril(jnp.ones((Q, Q), bool))
    # mask BEFORE the exp: above the diagonal rel > 0 can overflow to inf,
    # and where(mask, exp(rel), 0) would then backpropagate 0 * inf = NaN
    L = jnp.exp(jnp.where(tri[None, None, :, :, None], rel, -jnp.inf))
    scores = jnp.einsum("bcqs,bcks->bcqk", Cc, Bc)  # (b, nc, Q, Q)
    y_diag = jnp.einsum(
        "bcqk,bcqkh,bckh,bckhp->bcqhp", scores, L, dtc, xc
    )

    # Chunk states: S_c = sum_j exp(cum_last - cum_j) dt_j B_j (x) x_j
    suffix = jnp.exp(cum[:, :, -1:, :] - cum)  # (b, nc, Q, h)
    S_c = jnp.einsum("bcks,bckh,bckhp->bchsp", Bc, suffix * dtc, xc)

    # Inter-chunk scan: S_prev_{c} = exp(total_c-1) * S_prev_{c-1} + S_{c-1}
    total = jnp.exp(cum[:, :, -1, :])  # (b, nc, h) per-chunk total decay

    def scan_fn(S, inp):
        S_chunk, tot = inp  # (b, h, ds, p), (b, h)
        S_next = S * tot[..., None, None] + S_chunk
        return S_next, S

    S0 = (
        jnp.zeros((b, h, ds, p), jnp.float32)
        if initial_state is None
        else initial_state
    )
    S_last, S_prevs = jax.lax.scan(
        scan_fn,
        S0,
        (S_c.transpose(1, 0, 2, 3, 4), total.transpose(1, 0, 2)),
    )
    S_prevs = S_prevs.transpose(1, 0, 2, 3, 4)  # (b, nc, h, ds, p)

    # Off-diagonal term: y_off[i] = exp(cum_i) * C_i . S_prev
    y_off = jnp.einsum(
        "bcqs,bchsp,bcqh->bcqhp", Cc, S_prevs, jnp.exp(cum)
    )

    y = (y_diag + y_off).reshape(b, s, h, p)
    y = y + params["D"][None, None, :, None] * x
    y = y.reshape(b, s, d_in)

    # gated output norm + projection
    y = rms_norm(
        (y * jax.nn.silu(z.astype(jnp.float32))).astype(dt_),
        params["norm"],
        cfg.norm_eps,
    )
    y = constrain(y, "batch", None, "dinner")
    out = constrain(
        jnp.einsum("bse,ed->bsd", y, params["out_proj"].astype(dt_)),
        "batch", None, None,
    )
    # conv ring state: the last W-1 raw inputs, zero-left-padded when the
    # prompt is shorter (matching the causal conv's own left padding — a
    # negative slice start would silently hand decode a short window)
    W1 = cfg.ssm_conv - 1
    tail = xBC_raw[:, max(0, s - W1):, :]
    if s < W1:
        tail = jnp.pad(tail, ((0, 0), (W1 - s, 0), (0, 0)))
    state = {"S": S_last, "conv": tail}
    return out, state


def init_ssm_state(cfg, batch, dtype=jnp.float32):
    _, h, p, ds, conv_ch = _dims(cfg)
    return {
        "S": jnp.zeros((batch, h, ds, p), jnp.float32),
        "conv": jnp.zeros((batch, cfg.ssm_conv - 1, conv_ch), dtype),
    }


def mamba2_decode(params, cfg, u, state):
    """One-token step.  u: (b, 1, d); state: {"S","conv"}.  Returns (y, state)."""
    dt_ = u.dtype
    b = u.shape[0]
    d_in, h, p, ds, conv_ch = _dims(cfg)

    zxbcdt = jnp.einsum("bsd,de->bse", u, params["in_proj"].astype(dt_))
    z, x, B, C, dtraw = _split_proj(cfg, zxbcdt)
    xBC = jnp.concatenate([x, B, C], axis=-1)[:, 0]  # (b, conv_ch)

    # conv ring: window = [conv_state, new]
    win = jnp.concatenate([state["conv"], xBC[:, None, :]], axis=1)  # (b, W, c)
    w = params["conv_w"].astype(dt_)
    conv_out = jax.nn.silu(
        jnp.einsum("bwc,wc->bc", win, w) + params["conv_b"].astype(dt_)
    )
    new_conv = win[:, 1:, :]
    x, B, C = jnp.split(conv_out, [d_in, d_in + ds], axis=-1)

    x = x.reshape(b, h, p).astype(jnp.float32)
    B = B.astype(jnp.float32)  # (b, ds)
    C = C.astype(jnp.float32)
    dt = jax.nn.softplus(
        dtraw[:, 0].astype(jnp.float32) + params["dt_bias"][None, :]
    )  # (b, h)
    A = -jnp.exp(params["A_log"])
    decay = jnp.exp(dt * A[None, :])  # (b, h)

    S = state["S"] * decay[..., None, None] + jnp.einsum(
        "bs,bh,bhp->bhsp", B, dt, x
    )
    y = jnp.einsum("bs,bhsp->bhp", C, S) + params["D"][None, :, None] * x
    y = y.reshape(b, 1, d_in)
    y = rms_norm(
        (y * jax.nn.silu(z.astype(jnp.float32))).astype(dt_),
        params["norm"],
        cfg.norm_eps,
    )
    out = jnp.einsum("bse,ed->bsd", y, params["out_proj"].astype(dt_))
    return out, {"S": S, "conv": new_conv}
