"""PartitionSpec trees for parameters, optimizer state, batches, and caches.

``param_specs`` walks the abstract parameter pytree and applies ``_rule`` per
leaf.  Rules are expressed with NEGATIVE axis indices against the leaf's
CANONICAL (unstacked) rank, so scanned-layer stacks — which prepend one or
two stack dims — can never be sharded by accident:

    attn  wq/wk/wv (..., d, h, hd)   -> heads at -2
    attn  wo       (..., h, hd, d)   -> heads at -3
    mlp   wi       (..., d, 2, ff)   -> ff    at -1
    mlp   wo       (..., ff, d)      -> ff    at -2
    moe   wi       (..., E, d, 2, f) -> E at -4, else expert-ff at -1
    moe   wo       (..., E, f, d)    -> E at -3, else expert-ff at -2
    embed          (V, d)            -> vocab at -2 (vocab is padded to 128)
    mamba in_proj / out_proj         -> column / row parallel

Every assignment is guarded by divisibility against the model-axis size;
head_dim and stack dims are never sharded.  ZeRO-1 optimizer specs
additionally shard the first still-replicated divisible axis over the data
axes (``opt_state_specs``), which is what makes XLA materialize the
reduce-scatter/all-gather pair at the optimizer boundary (DESIGN.md §8).
"""
from __future__ import annotations

import math

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

__all__ = [
    "auto_mesh",
    "param_specs",
    "opt_state_specs",
    "batch_specs",
    "cache_specs",
    "named_shardings",
]

_ATTN_PARENTS = ("attn", "self_attn", "cross_attn")


# ----------------------------------------------------------------- rules
def _rule(name, shape, model: int, *, parent=None, n_experts: int = 0):
    """Sharding rule for one leaf: list of mesh-axis names (len == rank)."""
    spec = [None] * len(shape)
    rank = len(shape)

    def shard(ax: int):
        """Shard negative axis ``ax`` over 'model' when valid & divisible."""
        if -ax <= rank and model > 1 and shape[ax] % model == 0:
            spec[rank + ax] = "model"

    if n_experts and name in ("wi", "wo"):
        # MoE expert weights: canonical wi (E, d, 2, f) / wo (E, f, d).
        e_ax = -4 if name == "wi" else -3
        if -e_ax <= rank and shape[e_ax] == n_experts and n_experts % model == 0:
            shard(e_ax)
        else:  # experts indivisible (qwen 60) -> shard the expert-ff dim
            shard(-1 if name == "wi" else -2)
        return spec
    if parent in _ATTN_PARENTS:
        if name in ("wq", "wk", "wv"):
            shard(-2)
        elif name == "wo":
            shard(-3)
        elif name == "wkv_b":  # MLA latent -> per-head (r, h, nope + v)
            shard(-2)
        return spec
    if parent == "mlp":
        if name == "wi":
            shard(-1)
        elif name == "wo":
            shard(-2)
        return spec
    if parent == "mamba":
        if name == "in_proj":
            shard(-1)  # column-parallel over the packed zxBCdt projection
        elif name == "out_proj":
            shard(-2)  # row-parallel over d_inner
        return spec
    if name == "embed":
        shard(-2)  # vocab axis; padded to a multiple of 128
        return spec
    if name == "router":
        shard(-1)
        return spec
    return spec  # norms, biases, scalars: replicated


def _keys_of(path) -> list[str]:
    return [str(getattr(k, "key", k)) for k in path]


def _parent_of(keys) -> str | None:
    for k in reversed(keys[:-1]):
        if k in _ATTN_PARENTS:
            return "attn"
        if k in ("mlp", "moe", "mamba"):
            return k
    return None


def param_specs(params_abs, mesh, *, n_experts: int = 0):
    """PartitionSpec pytree matching ``params_abs`` for ``mesh``."""
    model = dict(mesh.shape).get("model", 1)

    def leaf_spec(path, leaf):
        keys = _keys_of(path)
        name, parent = keys[-1], _parent_of(keys)
        if parent == "moe":
            # shared experts are dense mlp weights living under the moe dict
            if name in ("shared_wi", "shared_wo"):
                return P(*_rule("w" + name[-1], leaf.shape, model, parent="mlp"))
            ne = n_experts if name in ("wi", "wo") else 0
            return P(*_rule(name, leaf.shape, model, n_experts=ne))
        return P(*_rule(name, leaf.shape, model, parent=parent))

    flat, treedef = jax.tree_util.tree_flatten_with_path(params_abs)
    return jax.tree_util.tree_unflatten(
        treedef, [leaf_spec(p, l) for p, l in flat]
    )


# -------------------------------------------------------------- optimizer
def opt_state_specs(params_abs, pspecs, mesh, *, zero1: bool = True):
    """Specs for per-parameter optimizer tensors (m/v/f32 masters).

    With ``zero1`` the first axis that is still replicated in the parameter
    spec and divides the data-axis product additionally shards over the data
    axes — classic ZeRO-1 state partitioning on top of tensor parallelism.
    """
    sizes = dict(mesh.shape)
    data_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dsize = math.prod(sizes[a] for a in data_axes) if data_axes else 1

    def z(leaf, spec):
        if not zero1 or dsize <= 1:
            return spec
        entries = list(spec) + [None] * (len(leaf.shape) - len(spec))
        for i, e in enumerate(entries):
            if e is None and leaf.shape[i] % dsize == 0 and leaf.shape[i] > 0:
                entries[i] = data_axes[0] if len(data_axes) == 1 else data_axes
                break
        return P(*entries)

    return jax.tree_util.tree_map(
        z, params_abs, pspecs, is_leaf=lambda x: isinstance(x, P)
    )


# ------------------------------------------------------------------ batch
def _is_abstract(x) -> bool:
    return hasattr(x, "shape") and hasattr(x, "dtype")


def batch_specs(batch_abs, mesh):
    """Shard the leading (global-batch) axis of every leaf over the data axes."""
    sizes = dict(mesh.shape)
    data_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)

    def spec(leaf):
        if not leaf.shape:
            return P()
        axes = data_axes
        while axes and leaf.shape[0] % math.prod(sizes[a] for a in axes):
            axes = axes[:-1]
        if not axes:
            return P(*([None] * len(leaf.shape)))
        first = axes[0] if len(axes) == 1 else axes
        return P(first, *([None] * (len(leaf.shape) - 1)))

    return jax.tree_util.tree_map(spec, batch_abs, is_leaf=_is_abstract)


# ------------------------------------------------------------------ cache
# canonical (unstacked) rank and (batch_axis, model_axis) per cache leaf name;
# model_axis None = never tensor-sharded.  Leading extra dims are layer /
# group stacks and stay unsharded.
_CACHE_RULES = {
    "k": (4, 0, 2),     # (b, S, g, hd): batch at 0, kv heads at 2
    "v": (4, 0, 2),
    "gk": (4, 0, 2),
    "gv": (4, 0, 2),
    "lk": (4, 0, 2),
    "lv": (4, 0, 2),
    "c_kv": (3, 0, None),  # MLA latent rows (b, S, rank): shared by heads
    "k_pe": (3, 0, None),  # MLA rope key rows (b, S, rope)
    "ks": (2, 0, 1),    # int8 dequant scales (b, g)
    "vs": (2, 0, 1),
    "enc": (3, 0, None),  # encoder states (b, F, d)
    "S": (4, 0, None),    # SSM state (b, h, ds, p)
    "conv": (3, 0, None),  # conv ring (b, W, c)
}


def cache_specs(cache_abs, mesh, *, paged_pool: bool = False):
    """PartitionSpec tree for a decode cache: batch over data, KV heads over
    model when divisible; scan-stack dims and scalars replicated.

    ``paged_pool=True`` reads the k/v leaves as the PAGED pool layout
    (L, n_pages, page_size, g, hd) — same canonical rank with the page
    pool standing in for the batch axis and the within-page axis for the
    sequence axis (DESIGN.md §13).  The rules carry over unchanged except
    the GQA fallback: within-page offsets are far too small to shard, so
    indivisible KV heads fall back on the page-POOL axis instead.
    """
    sizes = dict(mesh.shape)
    data_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dsize = math.prod(sizes[a] for a in data_axes) if data_axes else 1
    model = sizes.get("model", 1)

    def spec(path, leaf):
        name = _keys_of(path)[-1]
        rank = len(leaf.shape)
        rule = _CACHE_RULES.get(name)
        if rule is None or rank < rule[0]:
            return P(*([None] * rank))
        canon, b_ax, m_ax = rule
        extra = rank - canon
        entries = [None] * rank
        if dsize > 1 and leaf.shape[extra + b_ax] % dsize == 0:
            entries[extra + b_ax] = (
                data_axes[0] if len(data_axes) == 1 else data_axes
            )
        if m_ax is not None and model > 1:
            if leaf.shape[extra + m_ax] % model == 0:
                entries[extra + m_ax] = "model"
            elif canon == 4 and paged_pool:
                # paged-pool GQA fallback: pages are interchangeable, so
                # spread the page-pool axis over "model" (stacking on top
                # of any data-axis assignment when the divisibility holds)
                # rather than the tiny within-page axis.
                cur = entries[extra + b_ax]
                if cur is None:
                    if leaf.shape[extra + b_ax] % model == 0:
                        entries[extra + b_ax] = "model"
                elif leaf.shape[extra + b_ax] % (dsize * model) == 0:
                    prev = cur if isinstance(cur, tuple) else (cur,)
                    entries[extra + b_ax] = prev + ("model",)
            elif canon == 4 and leaf.shape[extra + 1] % model == 0:
                # KV heads don't divide the model axis (GQA with few KV
                # heads, e.g. 8 heads on a 16-wide axis): shard the SEQUENCE
                # axis of the (b, S, g, hd) cache instead.  Attention over a
                # seq-sharded cache partitions as partial scores + the
                # softmax-stat reductions XLA inserts; the decode-step
                # cache update at a dynamic position lowers to a
                # shard-local dynamic-update-slice.  Without this fallback
                # such caches replicate over the whole model axis — 16x the
                # HBM for the dominant decode buffer.
                entries[extra + 1] = "model"
        return P(*entries)

    flat, treedef = jax.tree_util.tree_flatten_with_path(cache_abs)
    return jax.tree_util.tree_unflatten(
        treedef, [spec(p, l) for p, l in flat]
    )


# ------------------------------------------------------------------- misc
def named_shardings(specs, mesh):
    """Map a pytree of PartitionSpecs (or one bare spec) to NamedShardings."""
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P),
    )


def auto_mesh(shape, axes, *, devices=None):
    """A mesh with every axis ``Auto``: the program places data with these
    specs and ``with_sharding_constraint`` and lets the partitioner
    propagate, which an ``Explicit`` mesh (``jax.make_mesh``'s default)
    refuses.  Without ``devices``, ``jax.make_mesh`` lays the fleet out in
    its physical order; given ``devices``, the mesh keeps their order."""
    auto = (jax.sharding.AxisType.Auto,) * len(axes)
    if devices is None:
        return jax.make_mesh(shape, axes, auto)
    return jax.sharding.Mesh(np.asarray(devices).reshape(shape), axes,
                             axis_types=auto)
