"""Loss and train step: next-token CE, grad accumulation, AdamW, metrics.

The step is a single jit-able function suitable for pjit lowering: batch in,
(params, opt_state, metrics) out.  Microbatching (grad accumulation) runs as
a lax.scan over batch splits — each microbatch's backward overlaps the
previous one's gradient reduction under XLA's scheduler (DESIGN.md §5).

The vocab axis stays model-sharded through the loss: log-sum-exp and label
gathers are computed on sharded logits (XLA inserts the small psums), so the
full (b, s, V) logits never materialize replicated.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models import train_logits
from .optimizer import AdamWConfig, adamw_update

__all__ = ["make_loss_fn", "make_train_step"]

AUX_COEF = 0.01


def make_loss_fn(cfg):
    def loss_fn(params, batch):
        tokens = batch["tokens"]  # (b, s+1)
        inputs = dict(batch, tokens=tokens[:, :-1])
        labels = tokens[:, 1:]
        logits, aux = train_logits(cfg, params, inputs)  # (b, s, V)
        logits = logits.astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        ce = jnp.mean(lse - gold)
        return ce + AUX_COEF * aux, (ce, aux)

    return loss_fn


def make_train_step(
    cfg, opt_cfg: AdamWConfig, *, microbatches: int = 1, grad_shardings=None,
    dp_axis: str | None = None, rns_codec=None, rns_repair: bool = False,
    transport_hook=None,
):
    """grad_shardings: optional NamedSharding tree matching params.  Pins
    gradients to the PARAMETER sharding so ZeRO-1's differently-sharded
    optimizer moments reshard at the optimizer boundary (reduce-scatter /
    all-gather) instead of leaking their sharding into the backward pass
    (measured: un-pinned, the partitioner partially shards attention dots by
    head_dim and all-reduces every score block).

    dp_axis: the mesh axis the step is data-parallel over (the step then
    runs under shard_map).  Gradients are averaged across it by a plain
    fp32 pmean, or through ``rns_codec`` when one is given; loss metrics
    are pmean'd over it either way.

    rns_codec: optional ``dist.grad_codec.GradCodec`` (needs ``dp_axis``):
    local gradients encode to residue channels, the WHOLE pytree
    all-reduces in a single bucketed per-channel int32 psum
    (``tree_pack``), and the fused decode runs inside ``adamw_update`` at
    the optimizer boundary — the paper's exact, order-independent
    aggregation on the real hot path (DESIGN.md §9).

    rns_repair: with a locate-and-correct codec (``make(correct=True)``),
    run RRNS repair on the local wire buffer before the psum: any single
    corrupted channel per element is rebuilt from the surviving channels in
    place instead of poisoning the all-reduce (DESIGN.md §10).  Adds a
    ``repaired`` metric (global count of corrected elements).

    transport_hook: optional ``buf -> buf`` applied to the packed
    channel-major wire buffer between encode and repair/psum — the seam
    where wire corruption lives, used by fault-injection tests and the
    ``--rns-correct`` smoke demo."""
    if rns_repair and (rns_codec is None or rns_codec.mb is None):
        raise ValueError(
            "rns_repair requires a locate-and-correct codec: "
            "GradCodec.make(correct=True)"
        )
    if rns_codec is not None and dp_axis is None:
        raise ValueError("rns_codec needs dp_axis (the all-reduce axis)")
    loss_fn = make_loss_fn(cfg)
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def pin(grads):
        if grad_shardings is None:
            return grads
        return jax.lax.with_sharding_constraint(grads, grad_shardings)

    def train_step(params, opt_state, batch):
        if microbatches == 1:
            (loss, (ce, aux)), grads = grad_fn(params, batch)
            grads = pin(grads)
        else:
            def split(x):
                b = x.shape[0]
                return x.reshape(microbatches, b // microbatches, *x.shape[1:])

            mb = jax.tree_util.tree_map(split, batch)
            zeros = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params
            )

            def acc_step(carry, mbatch):
                g_acc, l_acc, c_acc, a_acc = carry
                (l, (c, a)), g = grad_fn(params, mbatch)
                g_acc = jax.tree_util.tree_map(
                    lambda x, y: x + y.astype(jnp.float32), g_acc, g
                )
                return (g_acc, l_acc + l, c_acc + c, a_acc + a), None

            (grads, loss, ce, aux), _ = jax.lax.scan(
                acc_step, (zeros, 0.0, 0.0, 0.0), mb
            )
            inv = 1.0 / microbatches
            grads = pin(jax.tree_util.tree_map(lambda g: g * inv, grads))
            loss, ce, aux = loss * inv, ce * inv, aux * inv

        if rns_codec is None:
            if dp_axis is not None:
                grads = jax.lax.pmean(grads, dp_axis)
            params, opt_state, gnorm = adamw_update(
                opt_cfg, params, grads, opt_state
            )
        else:
            import dataclasses

            from repro.dist.grad_codec import tree_decode, tree_pack_rns

            # the wire buffer travels TYPED: one channel-major RnsArray
            # (layout BASE_MA/RRNS per the codec) from encode through
            # repair, psum, and the optimizer-boundary decode
            wire, meta = tree_pack_rns(rns_codec, grads)
            if transport_hook is not None:  # fault-injection seam (raw)
                wire = dataclasses.replace(
                    wire, residues=transport_hook(wire.residues)
                )
            repaired = unrepairable = None
            if rns_repair:
                # RRNS locate-and-correct on the local wire array: fresh
                # encodings (wraps=0), so single-channel location is exact
                # and the repaired buffer enters the psum as if the
                # corruption never happened
                wire, fault = rns_codec.correct_packed(wire)
                repaired = jax.lax.psum(
                    jnp.sum(fault >= 0).astype(jnp.int32), dp_axis
                )
                unrepairable = jax.lax.psum(
                    jnp.sum(fault == -2).astype(jnp.int32), dp_axis
                )
            summed = jax.lax.psum(wire, dp_axis)  # the ONLY grad collective
            nd = jax.lax.psum(1.0, dp_axis)      # trace-time constant
            params, opt_state, gnorm = adamw_update(
                opt_cfg, params, summed, opt_state,
                grad_decode=lambda s: tree_decode(
                    rns_codec, s, meta, denom=nd
                ),
            )
        if dp_axis is not None:
            loss, ce, aux = (
                jax.lax.pmean(x, dp_axis) for x in (loss, ce, aux)
            )
        # the optimizer's post-update step counter rides along so drivers
        # can sanity-check a checkpoint resume against the loop's own step
        metrics = {"loss": loss, "ce": ce, "aux": aux, "gnorm": gnorm,
                   "opt_step": opt_state["step"]}
        if rns_codec is not None and rns_repair:
            metrics["repaired"] = repaired
            metrics["unrepairable"] = unrepairable
        return params, opt_state, metrics

    return train_step
