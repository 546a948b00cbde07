"""Plain reference of Moonlight-16B-A3B as the program runs it, for the
comparison that decides ``correct``.

Float32 everywhere, matrix products at ``highest`` precision, latent
attention in its expanded form only (per-head K and V from the latent
rows), and the routed experts dropless and dense: every expert runs on
every token and the gates zero all but the chosen six.  It imports
nothing of the program and takes nothing the program made: it draws the
weights again from the seed, the way the program's initialiser draws
them (normal * 0.02 in float32, rounded to the configuration's bfloat16;
router and its correction bias kept in float32; norms zero; layer i from
the i-th of the layer keys whatever its kind; the untied head from
``fold_in`` of the embedding's key), one layer at a time.

Per layer, with no q compression:

    q = x W_q (H x (nope + rope)),  [c_kv ; k_pe] = x W_kva,
    c_kv = RMSNorm(c_kv),  rope on q_pe and k_pe (shared by the heads),
    [k_nope ; v] = c_kv W_kvb,  score = (q_nope k_nope + q_pe k_pe) / sqrt(192)
    out = softmax(score) v W_o

then layer 0's dense MLP (width 11264), or the router: sigmoid scores,
the top 6 of scores + e_score_correction_bias choose, the unbiased scores
of those six, normalised to 1 and times 2.446, weight them; the two
shared experts added ungated.  It follows the program where the program
departs from the published model (the configuration file lists each
departure): embedding scaled by sqrt(d), RMSNorm weight applied as
(1 + w), rope over halves.

``readings`` runs the sampled sequences (prompt + served tokens) through
the reference and compares with it every latent row (``c_kv`` with
``k_pe``) the program wrote into its paged pool for them, at every
layer, and every served token.  ``control=True`` reads the control
instead: the same forward with every weight (per-tensor scale) and every
activation, residual stream and logit row (per-row scale) rounded to
float8 e4m3, products accumulated in float32; its own latent rows
against the reference's, and its gaps read at the token it puts first.
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

# Limits, set from readings (PERF.md): the median relative error of the
# latent rows that the prefill and the decode wrote (program 0.0146-0.0148
# on the chip, float8 control 0.10-0.11 on the CPU at 4 layers), and the
# widest gap of a served token below the reference's best (program
# 0.58-1.08 on the chip: with an untied head a served token's margin is a
# fraction of a logit, and a top-6 choice that flips under rounding moves
# it; a token altered where it is produced reads 6.9-7.2).
KV_PROMPT_LIMIT = 0.07
KV_DECODE_LIMIT = 0.07
GAP_LIMIT = 2.5


@dataclasses.dataclass(frozen=True)
class Shapes:
    d: int
    H: int
    r: int
    dn: int
    dr: int
    dv: int
    L: int
    nd: int
    V: int
    E: int
    K: int
    f: int
    fs: int
    fd: int
    scale: float
    theta: float
    eps: float
    T: int
    wdt: str
    blk: int
    rows: int

    @classmethod
    def of(cls, spec: dict) -> "Shapes":
        T = spec["engine"]["cache_len"]
        return cls(d=spec["hidden_size"], H=spec["num_attention_heads"],
                   r=spec["kv_lora_rank"], dn=spec["qk_nope_head_dim"],
                   dr=spec["qk_rope_head_dim"], dv=spec["v_head_dim"],
                   L=spec["num_hidden_layers"],
                   nd=spec["first_k_dense_replace"], V=spec["vocab_size"],
                   E=spec["n_routed_experts"], K=spec["num_experts_per_tok"],
                   f=spec["moe_intermediate_size"],
                   fs=spec["n_shared_experts"] * spec["moe_intermediate_size"],
                   fd=spec["intermediate_size"],
                   scale=float(spec["routed_scaling_factor"]),
                   theta=float(spec["rope_theta"]),
                   eps=float(spec["rms_norm_eps"]), T=T,
                   wdt=spec["engine"]["param_dtype"],
                   blk=min(BLOCK, T), rows=min(ROWS, T))


def _w(key, shape, dt):
    """One weight as drawn by the program, widened back to float32."""
    return (0.02 * jax.random.normal(key, shape, jnp.float32)).astype(
        dt).astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(0, 2))
def _table(s: Shapes, key, head: bool):
    """The embedding, or with ``head`` the untied output head."""
    kE, _ = jax.random.split(key)
    return _w(jax.random.fold_in(kE, 1) if head else kE, (s.V, s.d), s.wdt)


@functools.partial(jax.jit, static_argnums=(0, 2))
def _layer(s: Shapes, key, i: int):
    _, kL = jax.random.split(key)
    k = jax.random.split(kL, s.L)[i]
    k1, k2 = jax.random.split(k)
    a = jax.random.split(k1, 4)
    p = {"wq": _w(a[0], (s.d, s.H, s.dn + s.dr), s.wdt),
         "wkv_a": _w(a[1], (s.d, s.r + s.dr), s.wdt),
         "wkv_b": _w(a[2], (s.r, s.H, s.dn + s.dv), s.wdt),
         "wo": _w(a[3], (s.H, s.dv, s.d), s.wdt)}
    if i < s.nd:
        m1, m2 = jax.random.split(k2)
        return dict(p, mwi=_w(m1, (s.d, 2, s.fd), s.wdt),
                    mwo=_w(m2, (s.fd, s.d), s.wdt))
    m = jax.random.split(k2, 4)
    return dict(
        p,
        router=0.02 * jax.random.normal(m[0], (s.d, s.E), jnp.float32),
        bias=0.02 * jax.random.normal(jax.random.fold_in(k2, 4), (s.E,),
                                      jnp.float32),
        wi=_w(m[1], (s.E, s.d, 2, s.f), s.wdt),
        we=_w(m[2], (s.E, s.f, s.d), s.wdt),
        swi=_w(m[3], (s.d, 2, s.fs), s.wdt),
        swo=_w(m[0], (s.fs, s.d), s.wdt))


def _fp8(x, axis=None):
    """Round to float8 e4m3 with a scale per tensor (axis None) or per
    slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    sc = jnp.maximum(amax, 1e-30) / 448.0
    return (x / sc).astype(jnp.float8_e4m3fn).astype(jnp.float32) * sc


def _quant(p, lp):
    if not lp:
        return p
    return {k: (v if k in ("router", "bias") else _fp8(v))
            for k, v in p.items()}


def _act(x, lp):
    return _fp8(x, axis=-1) if lp else x


def _norm(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def _rope(x, pos, theta):
    """x: (t, heads, dim) at positions pos (t,)."""
    half = x.shape[-1] // 2
    fr = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * fr
    c, s_ = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s_, x2 * c + x1 * s_], -1)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _latent(s: Shapes, p, x, lp):
    """The latent rows of a whole (T, d) sequence row: c_kv (T, r) after
    its norm, k_pe (T, rope) after rope."""
    p = _quant(p, lp)
    kv = _act(_norm(x, s.eps), lp) @ p["wkv_a"]
    c = _norm(kv[:, :s.r], s.eps)
    pe = _rope(kv[:, None, s.r:], jnp.arange(x.shape[0]), s.theta)[:, 0]
    return c, pe


@functools.partial(jax.jit, static_argnums=(0, 6))
def _block(s: Shapes, p, x, c, pe, q0, lp):
    """Layer output for rows q0 .. q0 + s.blk of one sequence."""
    p = _quant(p, lp)
    xb = jax.lax.dynamic_slice_in_dim(x, q0, s.blk)
    pos = q0 + jnp.arange(s.blk)
    h = _act(_norm(xb, s.eps), lp)
    q = jnp.einsum("td,dhk->thk", h, p["wq"])
    qn, qp = q[..., :s.dn], _rope(q[..., s.dn:], pos, s.theta)
    kv = jnp.einsum("sr,rhk->shk", _act(c, lp), p["wkv_b"])
    kn, v = kv[..., :s.dn], kv[..., s.dn:]
    sc = (jnp.einsum("thn,shn->hts", _act(qn, lp), _act(kn, lp))
          + jnp.einsum("the,se->hts", _act(qp, lp), _act(pe, lp))) \
        / math.sqrt(s.dn + s.dr)
    mask = jnp.arange(c.shape[0])[None, :] <= pos[:, None]
    pr = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
    o = jnp.einsum("hts,shv->thv", _act(pr, lp), _act(v, lp))
    xb = _act(xb + jnp.einsum("thv,hvd->td", _act(o, lp), p["wo"]), lp)
    h = _norm(xb, s.eps)
    ha = _act(h, lp)
    if "mwi" in p:                                   # the dense layer
        u = jnp.einsum("td,dgf->tgf", ha, p["mwi"])
        y = _act(jax.nn.silu(u[:, 0]) * u[:, 1], lp) @ p["mwo"]
        return _act(xb + y, lp)
    scores = jax.nn.sigmoid(h @ p["router"])
    _, idx = jax.lax.top_k(scores + p["bias"], s.K)
    g = jnp.take_along_axis(scores, idx, -1)
    g = g / jnp.sum(g, -1, keepdims=True) * s.scale
    gates = jnp.zeros((s.blk, s.E), jnp.float32).at[
        jnp.arange(s.blk)[:, None], idx].set(g)
    u = jnp.einsum("td,edgf->tegf", ha, p["wi"])
    ye = jnp.einsum("tef,efd->ted",
                    _act(jax.nn.silu(u[..., 0, :]) * u[..., 1, :], lp), p["we"])
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


def _advance(s: Shapes, p, x, c, pe, nblk, lp):
    """The layer's output for the first ``nblk`` blocks of rows of x."""
    rows = [_block(s, p, x, c, pe, b * s.blk, lp) for b in range(nblk)]
    return jax.lax.dynamic_update_slice_in_dim(
        x, jnp.concatenate(rows, 0), 0, 0)


@jax.jit
def _row_err(c, pe, cp, pp):
    """Relative error of each latent row (cp, pp) against (c, pe): the
    norm of the difference, c_kv and k_pe together, over the norm of the
    reference row."""
    cp, pp = cp.astype(jnp.float32), pp.astype(jnp.float32)
    num = jnp.sum((cp - c) ** 2, -1) + jnp.sum((pp - pe) ** 2, -1)
    den = jnp.sum(c * c, -1) + jnp.sum(pe * pe, -1)
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
    of the n latent rows the sequence wrote, per layer.  The rows
    compared are the program's (``seq["c_kv"]``, (L, n, r), and
    ``seq["k_pe"]``, (L, n, rope); zeros where the sequence holds none)
    or, with ``control``, the float8 forward's, run beside the reference
    on the same tokens."""
    s = Shapes.of(spec)
    key = jax.random.key(seed)
    with jax.default_matmul_precision("highest"):
        table = _table(s, key, False)
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
        del table
        errs = [np.zeros((s.L, n), np.float32) for n in ns]
        for i in range(s.L):
            p = _layer(s, key, i)
            for j, q in enumerate(seqs):
                c, pe = _latent(s, p, xs[j], False)
                if control:
                    cc, pc = _latent(s, p, xc[j], True)
                    xc[j] = _advance(s, p, xc[j], cc, pc, nblk[j], True)
                elif "c_kv" in q:
                    cc = _pad_rows(np.asarray(q["c_kv"][i])[:ns[j]], s.T)
                    pc = _pad_rows(np.asarray(q["k_pe"][i])[:ns[j]], s.T)
                else:
                    cc = None
                if cc is not None:
                    errs[j][i] = np.asarray(_row_err(c, pe, cc, pc))[:ns[j]]
                xs[j] = _advance(s, p, xs[j], c, pe, nblk[j], False)
            del p
        head = _table(s, key, True)
        logits, clogits = [], []
        for j, q in enumerate(seqs):
            p0, m = len(q["prompt"]) - 1, len(q["out"])
            for lp, x, dst in ((False, xs[j], logits),
                               (True, xc[j] if control else None, clogits)):
                if x is None:
                    dst.append(None)
                    continue
                x = jnp.concatenate([x, jnp.zeros((s.rows, s.d), x.dtype)], 0)
                parts = [np.asarray(_head(s, head, jax.lax.dynamic_slice_in_dim(
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

    ``kv_prompt_err`` and ``kv_decode_err``: the latent rows each
    sequence wrote, against the reference's, as a relative row error
    (``_row_err``); the median over the prompt's rows (written by the
    bucketed prefill, expanded form) or over the served tokens' rows
    (written by the paged decode, absorbed form), the largest over layers
    and sequences.  The median stands against a top-6 routing choice that
    flips under rounding: such a flip moves its own token's rows in later
    layers, and only a few tokens flip.  ``max_logit_gap``: the widest
    gap by which a served token's reference logit lies below the
    reference's best.  With ``control`` the float8 forward takes the
    program's place: its own rows, and the tokens it puts first.
    ``altered_gap`` (what one token altered where it is produced reads:
    the median, over every served position, of the gap of the token
    after the served one) and the per-layer medians are reported beside
    them."""
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
            "altered_gap": float(np.median(np.concatenate(alt))),
            "tokens": int(sum(len(g) for g in gaps)),
            "mismatches": int(sum((g > 0).sum() for g in gaps))}
