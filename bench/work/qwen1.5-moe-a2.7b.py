"""Operations and bytes of Qwen1.5-MoE-A2.7B, counted from the shapes and
each row's real length, whatever implements them.

Dropless: each token runs its ``top_k`` routed experts and the shared
ones; no capacity padding, no gathered whole-cache rows.  A multiply-add
counts 2 operations.  Bytes are those a step must move at least: the
weights it needs once, the KV it reads at the rows' real lengths and the
KV it writes.
"""
from __future__ import annotations


def _dims(spec):
    d, H = spec["hidden_size"], spec["num_attention_heads"]
    return dict(d=d, H=H, G=spec["num_key_value_heads"], hd=d // H,
                L=spec["num_hidden_layers"], V=spec["vocab_size"],
                E=spec["num_experts"], K=spec["num_experts_per_tok"],
                f=spec["moe_intermediate_size"],
                fs=spec["shared_expert_intermediate_size"])


def _wbytes(spec) -> int:
    return 2 if spec["engine"]["param_dtype"] == "bfloat16" else 4


def linear_flops_per_token(spec) -> int:
    """Every product of one token outside attention's scores, head
    included."""
    m = _dims(spec)
    d, hd = m["d"], m["hd"]
    attn = d * (m["H"] + 2 * m["G"]) * hd + m["H"] * hd * d
    routed = m["K"] * 3 * d * m["f"]
    shared = 3 * d * m["fs"]
    router = d * m["E"]
    return 2 * (m["L"] * (attn + routed + shared + router) + d * m["V"])


def attn_flops(spec, ctx: int) -> int:
    """Scores and weighted sum of one query over ``ctx`` positions, all
    layers."""
    m = _dims(spec)
    return 2 * 2 * m["L"] * m["H"] * m["hd"] * ctx


def token_flops(spec, ctx: int) -> int:
    return linear_flops_per_token(spec) + attn_flops(spec, ctx)


def prefill_flops(spec, start: int, n: int) -> int:
    """Tokens at positions start .. start + n - 1."""
    lin = linear_flops_per_token(spec) * n
    ctx = n * start + n * (n + 1) // 2
    return lin + attn_flops(spec, 1) * ctx


def weight_bytes(spec, experts_read: int) -> int:
    """Weights one step reads: everything held except the routed
    experts, of which ``experts_read`` per layer."""
    m = _dims(spec)
    d, hd, wb = m["d"], m["hd"], _wbytes(spec)
    attn = d * (m["H"] + 2 * m["G"]) * hd + m["H"] * hd * d
    per_layer = (attn + 3 * d * m["fs"]) * wb + d * m["E"] * 4 \
        + experts_read * 3 * d * m["f"] * wb
    return m["L"] * per_layer + m["V"] * d * wb


def kv_bytes_per_position(spec) -> int:
    m = _dims(spec)
    return m["L"] * 2 * m["G"] * m["hd"] * _wbytes(spec)


def decode_step(spec, ctx: list[int]) -> tuple[int, int]:
    """(operations, bytes) of one decode step whose active rows attend
    over ``ctx`` positions each (the new token included)."""
    m = _dims(spec)
    rows = len(ctx)
    flops = sum(token_flops(spec, c) for c in ctx)
    experts = m["E"] if rows * m["K"] >= m["E"] else rows * m["K"]
    kv = kv_bytes_per_position(spec)
    return flops, weight_bytes(spec, experts) + kv * (sum(ctx) + rows)
