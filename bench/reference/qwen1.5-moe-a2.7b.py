"""Plain reference of Qwen1.5-MoE-A2.7B as the program runs it, for the
comparison that decides ``correct``.

Float32 everywhere, matrix products at ``highest`` precision, and the
routed experts dropless: every token gets its top-4 experts, with no
capacity.  It imports nothing of the program and takes nothing the
program made: it draws the weights again from the seed, the way the
program's initialiser draws them (normal * 0.02 in float32, rounded to
the configuration's bfloat16, router kept in float32, norms zero), one
layer at a time, so that it fits beside nothing else on the chip.

It follows the program where the program departs from the published
model (the configuration file lists each departure): tied head, top-k
gates renormalised, no shared-expert gate, no q/k/v bias, embedding
scaled by sqrt(d) and RMSNorm weight applied as (1 + w).

``readings`` runs the sampled sequences (prompt + served tokens) through
the reference and compares with it every K/V row the program wrote into
its paged pool for them, at every layer, and every served token.
``control=True`` reads the control instead: the same forward computed in
float8 e4m3 where the program computes in bfloat16: every weight
(per-tensor scale) and every activation, residual stream and logit row
(per-row scale) rounded to float8, products accumulated in float32; its
own K/V rows against the reference's, and its gaps read at the token it
puts first.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 512          # query rows per attention / expert block (at most)
ROWS = 256           # logit rows per head call (at most)

# Limits, set from the program's and the float8 control's readings on the
# chip (PERF.md): the median relative error of the K/V rows that the
# prefill and the decode wrote, and the widest gap of a served token
# below the reference's best.
KV_PROMPT_LIMIT = 0.07
KV_DECODE_LIMIT = 0.07
GAP_LIMIT = 1.0


@dataclasses.dataclass(frozen=True)
class Shapes:
    d: int
    H: int
    G: int
    hd: int
    L: int
    V: int
    E: int
    K: int
    f: int
    fs: int
    theta: float
    eps: float
    T: int
    wdt: str
    blk: int
    rows: int

    @classmethod
    def of(cls, spec: dict) -> "Shapes":
        d, H = spec["hidden_size"], spec["num_attention_heads"]
        return cls(d=d, H=H, G=spec["num_key_value_heads"], hd=d // H,
                   L=spec["num_hidden_layers"], V=spec["vocab_size"],
                   E=spec["num_experts"], K=spec["num_experts_per_tok"],
                   f=spec["moe_intermediate_size"],
                   fs=spec["shared_expert_intermediate_size"],
                   theta=float(spec["rope_theta"]),
                   eps=float(spec["rms_norm_eps"]),
                   T=spec["engine"]["cache_len"],
                   wdt=spec["engine"]["param_dtype"],
                   blk=min(BLOCK, spec["engine"]["cache_len"]),
                   rows=min(ROWS, spec["engine"]["cache_len"]))


def _w(key, shape, dt):
    """One weight as drawn by the program, widened back to float32."""
    return (0.02 * jax.random.normal(key, shape, jnp.float32)).astype(
        dt).astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(0,))
def _embed_table(s: Shapes, key):
    kE, _ = jax.random.split(key)
    return _w(kE, (s.V, s.d), s.wdt)


@functools.partial(jax.jit, static_argnums=(0,))
def _layer(s: Shapes, key, i):
    _, kL = jax.random.split(key)
    k = jax.random.split(kL, s.L)[i]
    k1, k2 = jax.random.split(k)
    a = jax.random.split(k1, 4)
    m = jax.random.split(k2, 4)
    return {
        "wq": _w(a[0], (s.d, s.H, s.hd), s.wdt),
        "wk": _w(a[1], (s.d, s.G, s.hd), s.wdt),
        "wv": _w(a[2], (s.d, s.G, s.hd), s.wdt),
        "wo": _w(a[3], (s.H, s.hd, s.d), s.wdt),
        "router": 0.02 * jax.random.normal(m[0], (s.d, s.E), jnp.float32),
        "wi": _w(m[1], (s.E, s.d, 2, s.f), s.wdt),
        "we": _w(m[2], (s.E, s.f, s.d), s.wdt),
        "swi": _w(m[3], (s.d, 2, s.fs), s.wdt),
        "swo": _w(m[0], (s.fs, s.d), s.wdt),
    }


def _fp8(x, axis=None):
    """Round to float8 e4m3 with a scale per tensor (axis None) or per
    slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    sc = jnp.maximum(amax, 1e-30) / 448.0
    return (x / sc).astype(jnp.float8_e4m3fn).astype(jnp.float32) * sc


def _quant(p, lp):
    if not lp:
        return p
    return {k: (v if k == "router" else _fp8(v)) for k, v in p.items()}


def _act(x, lp):
    return _fp8(x, axis=-1) if lp else x


def _norm(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    fr = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * fr
    c, s_ = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s_, x2 * c + x1 * s_], -1)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _kv(s: Shapes, p, x, lp):
    """K and V of a whole (T, d) sequence row."""
    p = _quant(p, lp)
    h = _act(_norm(x, s.eps), lp)
    pos = jnp.arange(x.shape[0])
    k = _rope(jnp.einsum("td,dgk->tgk", h, p["wk"]), pos, s.theta)
    v = jnp.einsum("td,dgk->tgk", h, p["wv"])
    return k, v


@functools.partial(jax.jit, static_argnums=(0, 6))
def _block(s: Shapes, p, x, k, v, q0, lp):
    """Layer output for rows q0 .. q0 + s.blk of one sequence."""
    p = _quant(p, lp)
    xb = jax.lax.dynamic_slice_in_dim(x, q0, s.blk)
    pos = q0 + jnp.arange(s.blk)
    h = _act(_norm(xb, s.eps), lp)
    q = _rope(jnp.einsum("td,dhk->thk", h, p["wq"]), pos, s.theta)
    r = s.H // s.G
    q = q.reshape(s.blk, s.G, r, s.hd) / math.sqrt(s.hd)
    sc = jnp.einsum("tgrk,sgk->grts", _act(q, lp), _act(k, lp))
    mask = jnp.arange(k.shape[0])[None, :] <= pos[:, None]
    sc = jnp.where(mask, sc, -jnp.inf)
    pr = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("grts,sgk->tgrk", _act(pr, lp), _act(v, lp))
    o = o.reshape(s.blk, s.H, s.hd)
    xb = _act(xb + jnp.einsum("thk,hkd->td", _act(o, lp), p["wo"]), lp)
    h = _norm(xb, s.eps)
    probs = jax.nn.softmax(h @ p["router"], axis=-1)
    g, idx = jax.lax.top_k(probs, s.K)
    g = g / jnp.sum(g, -1, keepdims=True)
    gates = jnp.zeros((s.blk, s.E), jnp.float32).at[
        jnp.arange(s.blk)[:, None], idx].set(g)
    ha = _act(h, lp)
    u = jnp.einsum("td,edgf->tegf", ha, p["wi"])
    ye = jnp.einsum("tef,efd->ted", _act(jax.nn.silu(u[..., 0, :]) * u[..., 1, :], lp),
                    p["we"])
    y = jnp.einsum("te,ted->td", gates, ye)
    us = jnp.einsum("td,dgf->tgf", ha, p["swi"])
    y = y + _act(jax.nn.silu(us[:, 0]) * us[:, 1], lp) @ p["swo"]
    return _act(xb + y, lp)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _head(s: Shapes, table, x, lp):
    h = _norm(x, s.eps)
    if lp:
        return _act(_act(h, lp) @ _fp8(table).T, lp)
    return h @ table.T


def _advance(s: Shapes, p, x, k, v, nblk, lp):
    """The layer's output for the first ``nblk`` blocks of rows of x."""
    rows = [_block(s, p, x, k, v, b * s.blk, lp) for b in range(nblk)]
    return jax.lax.dynamic_update_slice_in_dim(
        x, jnp.concatenate(rows, 0), 0, 0)


@jax.jit
def _row_err(k, v, kp, vp):
    """Relative error of each K/V row (kp, vp) against (k, v): the norm of
    the difference over the row's heads, K and V together, over the
    norm of the reference row."""
    kp, vp = kp.astype(jnp.float32), vp.astype(jnp.float32)
    num = jnp.sum((kp - k) ** 2, (1, 2)) + jnp.sum((vp - v) ** 2, (1, 2))
    den = jnp.sum(k * k, (1, 2)) + jnp.sum(v * v, (1, 2))
    return jnp.sqrt(num / jnp.maximum(den, 1e-30))


def _pad_rows(x, T):
    out = np.zeros((T,) + x.shape[1:], x.dtype)
    out[:len(x)] = x
    return jnp.asarray(out)


def forward(spec: dict, seed: int, seqs: list, control: bool = False):
    """Run the float32 reference over each sequence ``{"prompt", "out"}``
    (the prompt and the served tokens, as the program wrote them).

    Returns, per sequence, the reference's logits at the served positions
    (row j predicts ``out[j]``), the control's logits there (``None``
    unless ``control``), and an (L, n) array: the relative error of each
    of the n K/V rows the sequence wrote, per layer.  The rows compared
    are the program's (``seq["k"]``, ``seq["v"]``, (L, n, g, hd); zeros
    where the sequence holds none) or, with ``control``, the float8
    forward's, run beside the reference on the same tokens."""
    s = Shapes.of(spec)
    key = jax.random.key(seed)
    with jax.default_matmul_precision("highest"):
        table = _embed_table(s, key)
        ns, nblk, xs, xc = [], [], [], []
        for q in seqs:
            toks = np.asarray(q["prompt"] + q["out"][:-1], np.int32)
            n = len(toks)
            pad = np.zeros(s.T, np.int32)
            pad[:n] = toks
            x = table[jnp.asarray(pad)] * jnp.float32(math.sqrt(s.d))
            xs.append(x)
            if control:
                xc.append(_act(x, True))
            ns.append(n)
            nblk.append(-(-n // s.blk))
        errs = [np.zeros((s.L, n), np.float32) for n in ns]
        for i in range(s.L):
            p = _layer(s, key, i)
            for j, q in enumerate(seqs):
                k, v = _kv(s, p, xs[j], False)
                if control:
                    kc, vc = _kv(s, p, xc[j], True)
                    xc[j] = _advance(s, p, xc[j], kc, vc, nblk[j], True)
                elif "k" in q:
                    kc = _pad_rows(np.asarray(q["k"][i])[:ns[j]], s.T)
                    vc = _pad_rows(np.asarray(q["v"][i])[:ns[j]], s.T)
                else:
                    kc = None
                if kc is not None:
                    errs[j][i] = np.asarray(_row_err(k, v, kc, vc))[:ns[j]]
                xs[j] = _advance(s, p, xs[j], k, v, nblk[j], False)
            del p
        logits, clogits = [], []
        for j, q in enumerate(seqs):
            p0, m = len(q["prompt"]) - 1, len(q["out"])
            for lp, x, dst in ((False, xs[j], logits),
                               (True, xc[j] if control else None, clogits)):
                if x is None:
                    dst.append(None)
                    continue
                x = jnp.concatenate([x, jnp.zeros((s.rows, s.d), x.dtype)], 0)
                parts = [np.asarray(_head(s, table, jax.lax.dynamic_slice_in_dim(
                    x, p0 + r0, s.rows), lp)) for r0 in range(0, m, s.rows)]
                dst.append(np.concatenate(parts, 0)[:m])
    return logits, clogits, errs


def forward_logits(spec: dict, seed: int, seqs: list, lp: bool = False):
    """Logits (n_i, V) at the served positions of each sequence: the
    reference's, or with ``lp`` the float8 forward's."""
    logits, clogits, _ = forward(spec, seed, seqs, control=lp)
    return clogits if lp else logits


def _worst_median(errs, lo, hi):
    """The largest, over sequences and layers, of the median row error
    over rows [lo_j, hi_j) of each sequence; None with no such rows."""
    meds = [np.median(e[:, a:b], axis=1) for e, a, b in zip(errs, lo, hi)
            if b > a]
    if not meds:
        return None, []
    per_layer = np.max(np.stack(meds), axis=0)
    return float(per_layer.max()), [float(x) for x in per_layer]


def readings(spec: dict, seed: int, seqs: list, control: bool = False):
    """The numbers compared, over the sampled sequences.

    ``kv_prompt_err`` and ``kv_decode_err``: the K/V rows each sequence
    wrote, against the reference's, as a relative row error
    (``_row_err``); the median over the prompt's rows (written by the
    bucketed prefill) or over the served tokens' rows (written by the
    paged decode), the largest over layers and sequences.  The median
    stands against a top-4 routing choice that flips under rounding:
    such a flip moves its own token's rows far, and only a few tokens
    flip.  ``max_logit_gap``: the widest gap by which a served token's
    reference logit lies below the reference's best.  With ``control``
    the float8 forward takes the program's place: its own rows, and the
    tokens it puts first.  ``altered_gap`` (the gap of the token after
    each served one, as a token altered where it is produced would read)
    and the per-layer medians are reported beside them."""
    ref, low, errs = forward(spec, seed, seqs, control)
    if control:
        picks = [np.argmax(l, -1) for l in low]
    else:
        picks = [np.asarray(q["out"]) for q in seqs]
    V = ref[0].shape[-1]
    gaps = [r.max(-1) - r[np.arange(len(pk)), pk] for r, pk in zip(ref, picks)]
    alt = [r.max(-1) - r[np.arange(len(pk)), (pk + 1) % V]
           for r, pk in zip(ref, picks)]
    plen = [len(q["prompt"]) for q in seqs]
    ends = [e.shape[1] for e in errs]
    kp, kp_l = _worst_median(errs, [0] * len(seqs), plen)
    kd, kd_l = _worst_median(errs, plen, ends)
    return {"kv_prompt_err": kp, "kv_decode_err": kd,
            "kv_prompt_by_layer": kp_l, "kv_decode_by_layer": kd_l,
            "kv_rows": int(sum(ends)),
            "max_logit_gap": max(float(g.max()) for g in gaps),
            "altered_gap": min(float(g.max()) for g in alt),
            "tokens": int(sum(len(g) for g in gaps)),
            "mismatches": int(sum((g > 0).sum() for g in gaps))}
