"""Operations and bytes of Moonlight-16B-A3B, counted from the shapes and
each row's real length, whatever implements them.

Dropless: each token runs its ``top_k`` routed experts and the shared
ones (the leading dense layers their MLP); no capacity padding, no pad
tokens, no gathered whole-cache rows.  A multiply-add counts 2
operations.  Latent attention counts in the form the program runs:

* decode, absorbed: per token and layer ``q_nope W_UKᵀ`` and ``o_lat
  W_UV`` (2·H·nope·rank and 2·H·rank·v), per position attended
  2·H·(rank + rope) for the scores and 2·H·rank for the weighted sum, and
  the position's latent row read, (rank + rope) values;
* prefill, expanded: each position's latent row expanded once to its
  per-head key and value (2·rank·H·(nope + v)), per (query, key) pair
  2·H·(nope + rope) + 2·H·v.

Bytes are those a step must move at least: the weights it needs once,
the latent rows it reads at the rows' real lengths and those it writes.
"""
from __future__ import annotations


def _dims(spec):
    return dict(d=spec["hidden_size"], H=spec["num_attention_heads"],
                r=spec["kv_lora_rank"], dn=spec["qk_nope_head_dim"],
                dr=spec["qk_rope_head_dim"], dv=spec["v_head_dim"],
                L=spec["num_hidden_layers"], nd=spec["first_k_dense_replace"],
                V=spec["vocab_size"], E=spec["n_routed_experts"],
                K=spec["num_experts_per_tok"], f=spec["moe_intermediate_size"],
                fs=spec["n_shared_experts"] * spec["moe_intermediate_size"],
                fd=spec["intermediate_size"])


def _wbytes(spec) -> int:
    return 2 if spec["engine"]["param_dtype"] == "bfloat16" else 4


def _proj(m) -> int:
    """Multiply-adds of one token's attention projections in one layer."""
    d, H, r, dn, dr, dv = (m[k] for k in ("d", "H", "r", "dn", "dr", "dv"))
    return d * H * (dn + dr) + d * (r + dr) + H * dv * d


def _ffn(m) -> int:
    """Multiply-adds of one token's feed-forward, all layers."""
    d = m["d"]
    experts = m["K"] * 3 * d * m["f"] + 3 * d * m["fs"] + d * m["E"]
    return m["nd"] * 3 * d * m["fd"] + (m["L"] - m["nd"]) * experts


def linear_flops_per_token(spec) -> int:
    """Every product of one decoded token outside its attention over the
    cache: projections, the absorbed form's W_UK and W_UV, the
    feed-forward, the head."""
    m = _dims(spec)
    absorb = m["H"] * m["r"] * (m["dn"] + m["dv"])
    return 2 * (m["L"] * (_proj(m) + absorb) + _ffn(m) + m["d"] * m["V"])


def attn_flops(spec, ctx: int) -> int:
    """Absorbed attention of one query over ``ctx`` positions, all
    layers: 2·H·(rank + rope) for the scores, 2·H·rank for the sum."""
    m = _dims(spec)
    return 2 * m["L"] * m["H"] * (2 * m["r"] + m["dr"]) * ctx


def token_flops(spec, ctx: int) -> int:
    return linear_flops_per_token(spec) + attn_flops(spec, ctx)


def _prefill_ops(spec, start: int, n: int, head_rows: int) -> int:
    m = _dims(spec)
    H = m["H"]
    lin = 2 * (m["L"] * _proj(m) + _ffn(m)) * n + 2 * m["d"] * m["V"] * head_rows
    expand = 2 * m["L"] * m["r"] * H * (m["dn"] + m["dv"]) * (start + n)
    pairs = n * start + n * (n + 1) // 2
    attn = 2 * m["L"] * H * (m["dn"] + m["dr"] + m["dv"]) * pairs
    return lin + expand + attn


def prefill_flops(spec, start: int, n: int) -> int:
    """Tokens at positions start .. start + n - 1, expanded form, the
    head on every token (the model's operations)."""
    return _prefill_ops(spec, start, n, n)


def weight_bytes(spec, experts_read: int) -> int:
    """Weights one step reads: everything held except the routed
    experts, of which ``experts_read`` per expert layer; embedding rows
    are not counted, the untied head is."""
    m = _dims(spec)
    d, wb = m["d"], _wbytes(spec)
    attn = _proj(m) + m["r"] * m["H"] * (m["dn"] + m["dv"])
    dense = m["nd"] * (attn + 3 * d * m["fd"]) * wb
    expert = (m["L"] - m["nd"]) * (
        (attn + 3 * d * m["fs"]) * wb + d * m["E"] * 4 + m["E"] * 4
        + experts_read * 3 * d * m["f"] * wb)
    return dense + expert + m["V"] * d * wb


def kv_bytes_per_position(spec) -> int:
    """One position's latent rows, all layers."""
    m = _dims(spec)
    return m["L"] * (m["r"] + m["dr"]) * _wbytes(spec)


def _experts(spec, tokens: int) -> int:
    m = _dims(spec)
    return min(m["E"], tokens * m["K"])


def decode_step(spec, ctx: list[int]) -> tuple[int, int]:
    """(operations, bytes) of one decode step whose active rows attend
    over ``ctx`` positions each (the new token included)."""
    flops = sum(token_flops(spec, c) for c in ctx)
    kv = kv_bytes_per_position(spec)
    return flops, (weight_bytes(spec, _experts(spec, len(ctx)))
                   + kv * (sum(ctx) + len(ctx)))


def prefill_step(spec, start: int, n: int) -> tuple[int, int]:
    """(operations, bytes) of one admission's extend: n prompt tokens
    after ``start`` cached ones, the head on the last only (the program
    reads one logit row)."""
    kv = kv_bytes_per_position(spec)
    return (_prefill_ops(spec, start, n, 1),
            weight_bytes(spec, _experts(spec, n)) + kv * (start + 2 * n))
