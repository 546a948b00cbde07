"""Fault detection and repair for checkpoints and tensor transport.

Content fingerprints (sha256 over dtype/shape/bytes) catch single-bit flips
in saved or relayed tensors; ``find_restorable`` walks a checkpoint
directory newest-first and returns the first step whose manifest AND tensor
contents verify — torn saves (no manifest after the atomic-rename protocol
in train/checkpoint.py) and corrupt steps are skipped, which is what makes
resume elastic to mid-save crashes (DESIGN.md §8).

``repair_packed`` is the finer-grained companion for RNS-codec state: where
a fingerprint mismatch can only trigger a rollback to the previous verified
checkpoint, a codec built with ``GradCodec.make(correct=True)`` carries two
redundant residue channels, so a single corrupted channel per element is
located and CORRECTED in place (DESIGN.md §10) and the step keeps going.

``WireStore`` packages the detect/locate-and-correct plumbing as a keyed
store of typed ``RnsArray`` wire fingerprints — the serve engine keys it by
physical cache page (the paged pool, DESIGN.md §13), where one stored
codeword serves every reader of a shared page, and by ``("crypto", rid)``
for crypto lane slots (DESIGN.md §15).
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "tensor_fingerprint",
    "tree_fingerprints",
    "verify_fingerprints",
    "load_step",
    "load_verified",
    "scan_restorable",
    "find_restorable",
    "repair_packed",
    "WireStore",
]


def repair_packed(codec, packed, *, wraps: int = 0,
                  channel_major: bool = False):
    """Locate-and-correct a packed RNS codec buffer (wire or checkpoint).

    ``packed`` is leaf-major ``(..., n_channels)`` by default or the wire's
    channel-major ``(n_channels, B)`` with ``channel_major=True``; ``wraps``
    is 0 for fresh encodings / normalized sums / checkpointed codec state
    and ``world - 1`` for a raw post-psum buffer (see
    ``GradCodec.locate_fault``).

    Returns ``(repaired, report)`` where ``report`` is a host-side dict:
    ``repaired`` counts elements whose single bad channel was rebuilt from
    the survivors, ``unrecoverable`` counts elements with multi-channel
    corruption (left untouched — those still need the ``find_restorable``
    rollback path).  A clean buffer returns bitwise-unchanged with both
    counts zero.

    ``packed`` may also be a typed ``RnsArray`` (core/array.py) — its own
    channel axis then wins over ``channel_major``, and the repaired buffer
    comes back typed.
    """
    from repro.core.array import RnsArray

    if isinstance(packed, RnsArray):
        fixed, fault = codec.correct_packed(packed, wraps=wraps)
    else:
        buf = packed.T if channel_major else packed
        fixed, fault = codec.correct_packed(buf, wraps=wraps)
        fixed = fixed.T if channel_major else fixed
    report = {
        "repaired": int(jnp.sum(fault >= 0)),
        "unrecoverable": int(jnp.sum(fault == -2)),
    }
    return fixed, report


class WireStore:
    """Keyed store of typed RRNS wire fingerprints with detect/repair.

    Each entry is a channel-major ``RnsArray`` codeword (the output of
    ``codec.encode_array(..., channel_major=True)``, or ``host_array`` of
    residues already read back) under an arbitrary hashable key — the
    serve engine uses physical page ids of the paged pool, where ONE
    stored codeword covers every reader of a shared page: corrupt it and every reader's verify fails;
    repair it once and every reader re-verifies.  ``matches`` compares on
    the host, so two host codewords take no device op; ``repair`` and
    ``corrupt`` leave the entry's residues on the host.

    ``stats`` accumulates across the store's lifetime:
      verified / failed           — ``matches`` outcomes (content checks)
      wire_ok / wire_corrupt      — ``ok`` outcomes (codeword self-checks)
      repaired / unrecoverable    — summed ``repair`` reports
    """

    def __init__(self, codec):
        self.codec = codec
        self.raw: dict = {}
        self.stats = {"verified": 0, "failed": 0, "wire_ok": 0,
                      "wire_corrupt": 0, "repaired": 0, "unrecoverable": 0}

    def __contains__(self, key) -> bool:
        return key in self.raw

    def __len__(self) -> int:
        return len(self.raw)

    def keys(self):
        return self.raw.keys()

    def put(self, key, arr) -> None:
        self.raw[key] = arr

    def host_array(self, residues):
        """A channel-major ``(n_channels, B)`` codeword over HOST (NumPy)
        residues, typed as ``codec.as_array(..., channel_major=True)``
        types a device buffer."""
        from repro.core.array import RnsArray

        c = self.codec
        return RnsArray(np.ascontiguousarray(residues, np.int32), c.base,
                        layout=c.layout, signed=True, channel_axis=0,
                        mb=c.mb)

    def get(self, key):
        return self.raw[key]

    def pop(self, key, default=None):
        return self.raw.pop(key, default)

    def clear(self) -> None:
        self.raw.clear()

    def matches(self, key, fresh) -> bool:
        """Bitwise compare a freshly encoded codeword against the stored
        one — the content-integrity check (recomputed fingerprint vs the
        fingerprint taken when the data froze)."""
        ok = bool(np.array_equal(np.asarray(fresh.residues),
                                 np.asarray(self.raw[key].residues)))
        self.stats["verified" if ok else "failed"] += 1
        return ok

    def ok(self, key) -> bool:
        """Codeword self-consistency of the stored buffer (redundant-
        channel check) — detects corruption of the stored fingerprint
        itself, without touching the fingerprinted data."""
        good = bool(jnp.all(self.codec.verify_packed(self.raw[key])))
        self.stats["wire_ok" if good else "wire_corrupt"] += 1
        return good

    def repair(self, key) -> dict:
        """Locate-and-correct the stored codeword in place via
        ``repair_packed``; returns the per-call report dict."""
        fixed, report = repair_packed(self.codec, self.raw[key], wraps=0)
        self.raw[key] = dataclasses.replace(
            fixed, residues=np.asarray(fixed.residues))
        self.stats["repaired"] += report["repaired"]
        self.stats["unrecoverable"] += report["unrecoverable"]
        return report

    def corrupt(self, key, channel: int = 0, delta: int = 1,
                index: int = 0) -> None:
        """Fault injection for tests/drivers: modular-bump one residue of
        the stored codeword (stays a syntactically valid residue, so only
        the redundant channels can catch it)."""
        arr = self.raw[key]
        mods = tuple(self.codec.base.moduli) + self.codec.redundant
        m = mods[channel]
        res = np.array(arr.residues)
        res[channel, index] = (int(res[channel, index]) + delta) % m
        self.raw[key] = dataclasses.replace(arr, residues=res)


def tensor_fingerprint(arr) -> str:
    """Content hash of one (host or device) array: dtype, shape, raw bytes."""
    a = np.ascontiguousarray(np.asarray(arr))
    h = hashlib.sha256()
    h.update(str(a.dtype).encode())
    h.update(str(a.shape).encode())
    h.update(a.tobytes())
    return h.hexdigest()[:32]


def _flat_named(tree) -> list[tuple[str, object]]:
    leaves, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [
        ("/".join(str(getattr(k, "key", k)) for k in path), leaf)
        for path, leaf in leaves
    ]


def tree_fingerprints(tree) -> dict[str, str]:
    """{name: fingerprint} for every leaf, in flattening order."""
    return {name: tensor_fingerprint(leaf) for name, leaf in _flat_named(tree)}


def verify_fingerprints(tree, fingerprints: dict[str, str]) -> list[str]:
    """Names of leaves whose content does NOT match ``fingerprints``.

    A missing expected fingerprint counts as a mismatch; an empty list means
    the tree verifies clean.
    """
    bad = []
    for name, leaf in _flat_named(tree):
        if fingerprints.get(name) != tensor_fingerprint(leaf):
            bad.append(name)
    return bad


def load_step(path: str):
    """Load + verify one ``step_<N>`` dir: (manifest, {name: array}).

    Raises FileNotFoundError for a torn save (no manifest survived the
    atomic rename) or missing tensor file, IOError naming the bad leaves on
    fingerprint mismatch."""
    manifest_path = os.path.join(path, "manifest.json")
    if not os.path.exists(manifest_path):
        raise FileNotFoundError(f"no manifest under {path} (torn save?)")
    with open(manifest_path) as f:
        manifest = json.load(f)
    flat = {
        name: np.load(os.path.join(path, f"{i}.npy"))
        for i, name in enumerate(manifest["names"])
    }
    bad = verify_fingerprints(
        flat, dict(zip(manifest["names"], manifest["fingerprints"]))
    )
    if bad:
        raise IOError(f"checkpoint {path} corrupt: {bad}")
    return manifest, flat


def load_verified(path: str):
    """Quiet variant of ``load_step``: None for torn/unreadable/corrupt."""
    try:
        return load_step(path)
    except Exception:
        return None


def scan_restorable(ckpt_dir: str):
    """Newest fully-verified step: (path, manifest, {name: array}) or None.

    Returns the loaded-and-verified contents so callers (checkpoint.restore)
    don't pay a second full read + hash of a multi-GB checkpoint."""
    if not ckpt_dir or not os.path.isdir(ckpt_dir):
        return None
    steps = []
    for d in os.listdir(ckpt_dir):
        if d.startswith("step_") and not d.endswith(".tmp"):
            try:
                steps.append((int(d.split("_", 1)[1]), d))
            except ValueError:
                continue
    for _, d in sorted(steps, reverse=True):
        path = os.path.join(ckpt_dir, d)
        loaded = load_verified(path)
        if loaded is not None:
            return (path,) + loaded
    return None


def find_restorable(ckpt_dir: str) -> str | None:
    """Path of the newest fully-verified ``step_<N>`` directory, else None."""
    found = scan_restorable(ckpt_dir)
    return found[0] if found else None
