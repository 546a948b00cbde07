"""RRNS locate-and-correct gradient codec (DESIGN.md §10).

Tier-1 coverage (no optional deps): with the second redundant modulus
(``GradCodec.make(correct=True)``) every single corrupted channel must be
located and corrected back to a bitwise-identical buffer — for corruption in
base AND redundant channels, on buffers produced by both the jnp and fused
encode paths, and composed with ``normalize`` after signed sums.  Multi-
channel corruption must be refused (never silently miscorrected), and the
repair must ride the train step / launch driver end to end.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.dist.fault import repair_packed
from repro.dist.grad_codec import GradCodec, rns_psum, rns_psum_tree


def _mesh1():
    return Mesh(np.array(jax.devices()[:1]), ("data",))


def _chans(codec):
    return tuple(codec.base.moduli) + codec.redundant


def _corrupt(buf, ch: int, m: int, delta: int = 7):
    """Shift every element's channel ``ch`` by delta mod m (always a real,
    still-canonical corruption since 0 < delta < m)."""
    assert 0 < delta < m
    return buf.at[..., ch].set(jnp.mod(buf[..., ch] + delta, m))


# ----------------------------------------------------------- construction
def test_correct_codec_shape_and_redundant_ordering():
    codec = GradCodec.make(world=4, correct=True)
    assert codec.n_channels == codec.base.n + 2
    assert codec.mb is not None and codec.use_fused
    # the locate guarantee needs the redundant pair to dominate every base
    # pair product: redundant moduli must be the largest of the whole set
    assert min(codec.redundant) > max(codec.base.moduli)
    # detect-only codecs are untouched: same base, same wire format as ever
    plain = GradCodec.make(world=4)
    assert plain.mb is None and plain.n_channels == plain.base.n + 1


def test_locate_requires_second_redundant():
    plain = GradCodec.make(world=2)
    buf = plain.encode(jnp.asarray([1.0]))
    with pytest.raises(ValueError, match="correct=True"):
        plain.locate_fault(buf)
    with pytest.raises(ValueError, match="correct=True"):
        plain.correct_packed(buf)


# ---------------------------------------------------- every-channel repair
@pytest.mark.parametrize("fused", [True, False])
def test_correct_every_channel_roundtrip(fused):
    """The acceptance bar: corrupting ANY channel i of the (n+2)-channel
    encoding and running correct_packed yields a buffer bitwise-equal to the
    uncorrupted one — jnp and fused encode paths alike."""
    codec = GradCodec.make(world=4, correct=True, fused=fused)
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.standard_normal(257).astype(np.float32))
    buf = codec.encode_packed(g).astype(jnp.int32)  # fused or jnp encode
    for ch, m in enumerate(_chans(codec)):
        bad = _corrupt(buf, ch, int(m))
        fault = codec.locate_fault(bad)
        assert bool(jnp.all(fault == ch)), f"channel {ch} not located"
        fixed, fault2 = codec.correct_packed(bad)
        np.testing.assert_array_equal(np.asarray(fault2), np.asarray(fault))
        np.testing.assert_array_equal(np.asarray(fixed), np.asarray(buf))


def test_redundant_channel_corruption_does_not_misfire():
    """Corruption in a REDUNDANT channel must locate as that redundant
    channel — never as a base channel (which would 'repair' good data)."""
    codec = GradCodec.make(world=4, correct=True)
    rng = np.random.default_rng(1)
    g = jnp.asarray(rng.standard_normal(100).astype(np.float32))
    buf = codec.encode(g).astype(jnp.int32)
    n = codec.base.n
    for j, mr in enumerate(codec.redundant):
        for delta in (1, 17, int(mr) - 1):
            bad = _corrupt(buf, n + j, int(mr), delta)
            fault = codec.locate_fault(bad)
            assert bool(jnp.all(fault == n + j))
            fixed, _ = codec.correct_packed(bad)
            np.testing.assert_array_equal(np.asarray(fixed), np.asarray(buf))


def test_clean_buffer_is_untouched():
    codec = GradCodec.make(world=4, correct=True)
    g = jnp.asarray(
        np.random.default_rng(2).standard_normal(64).astype(np.float32)
    )
    buf = codec.encode(g).astype(jnp.int32)
    fault = codec.locate_fault(buf)
    assert bool(jnp.all(fault == -1))
    fixed, _ = codec.correct_packed(buf)
    np.testing.assert_array_equal(np.asarray(fixed), np.asarray(buf))


def test_two_channel_corruption_detected_but_refused():
    """More corruption than the code can correct must come back as -2 with
    the buffer passed through unmodified — never a silent miscorrection."""
    codec = GradCodec.make(world=4, correct=True)
    g = jnp.asarray(
        np.random.default_rng(3).standard_normal(128).astype(np.float32)
    )
    buf = codec.encode(g).astype(jnp.int32)
    chans = _chans(codec)
    for c1, c2 in [(0, 1), (0, 3), (2, 4), (3, 4)]:
        bad = _corrupt(_corrupt(buf, c1, int(chans[c1]), 5),
                       c2, int(chans[c2]), 11)
        fault = codec.locate_fault(bad)
        assert bool(jnp.all(fault == -2)), (c1, c2)
        fixed, _ = codec.correct_packed(bad)
        np.testing.assert_array_equal(np.asarray(fixed), np.asarray(bad))
        # and the cheap detector flags it too
        assert not bool(jnp.any(codec.verify_packed(bad)))


def test_verify_packed_two_redundant_channels():
    """With m_b the detector must catch corruption of EITHER redundant
    channel (the other still pins the true wrap count)."""
    codec = GradCodec.make(world=4, correct=True)
    g = jnp.asarray(
        np.random.default_rng(4).standard_normal(32).astype(np.float32)
    )
    folded = codec.fold(codec.encode(g).astype(jnp.int32))
    assert bool(jnp.all(codec.verify_packed(folded)))
    n = codec.base.n
    for j, mr in enumerate(codec.redundant):
        bad = _corrupt(folded, n + j, int(mr), 1)
        assert not bool(jnp.any(codec.verify_packed(bad)))


# ----------------------------------------- summed buffers (wraps) + queries
def test_correct_summed_buffer_then_normalize_sign():
    """Correction composed with normalize after signed sums: repair a
    corrupted post-psum buffer at wraps=world-1, then normalize re-anchors
    the redundant channels so Algorithm-1 sign queries apply to the sum."""
    W = 4
    codec = GradCodec.make(world=W, correct=True)
    rng = np.random.default_rng(5)
    gs = rng.standard_normal((W, 200)).astype(np.float32)
    summed = jnp.asarray(
        sum(np.asarray(codec.encode(jnp.asarray(x)), np.int64) for x in gs)
        .astype(np.int32)
    )
    folded = codec.fold(summed)  # the codeword of the integer sum S < W*M
    for ch in (0, codec.base.n, codec.base.n + 1):
        m = int(_chans(codec)[ch])
        bad = _corrupt(folded, ch, m, 5)
        fixed, fault = codec.correct_packed(bad, wraps=W - 1)
        assert bool(jnp.all(fault == ch))
        np.testing.assert_array_equal(np.asarray(fixed), np.asarray(folded))
        q = np.clip(
            np.round(gs.astype(np.float64) * (1 << codec.frac_bits)),
            -codec.qmax, codec.qmax,
        )
        np.testing.assert_array_equal(
            np.asarray(codec.is_negative(codec.normalize(fixed))),
            q.sum(0) < 0,
        )


def test_wraps_range_validates_against_survivor_product():
    codec = GradCodec.make(world=4, correct=True)
    buf = codec.encode(jnp.asarray([1.0])).astype(jnp.int32)
    with pytest.raises(ValueError, match="survivor"):
        codec.locate_fault(buf, wraps=1 << 16)  # R = (wraps+1)*M too wide


# ------------------------------------------------------- transport plumbing
@pytest.mark.parametrize("fused", [True, False])
def test_correct_codec_transport_matches_plain_decode(fused):
    """The (n+2)-channel wire format must flow through rns_psum and the
    bucketed rns_psum_tree unchanged: decoded gradients bitwise-match this
    codec's own jnp fold+decode oracle (the correct codec uses a different
    moduli set than the detect-only one, so that's the right reference)."""
    codec = GradCodec.make(world=2, correct=True, fused=fused)
    mesh = _mesh1()
    rng = np.random.default_rng(6)
    g = jnp.asarray(rng.standard_normal(300).astype(np.float32))
    out = jax.jit(jax.shard_map(lambda x: rns_psum(codec, x, "data"),
                                mesh=mesh, in_specs=P(), out_specs=P(),
                                check_vma=False))(g)
    want = codec.decode(codec.fold(codec.encode(g).astype(jnp.int32)))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))

    tree = {"a": g, "b": g[:37].reshape(37, 1) * 2.0}
    got = jax.jit(jax.shard_map(lambda t: rns_psum_tree(codec, t, "data"),
                                mesh=mesh, in_specs=(P(),), out_specs=P(),
                                check_vma=False))(tree)
    for leaf, ref in zip(jax.tree_util.tree_leaves(got),
                         jax.tree_util.tree_leaves(tree)):
        np.testing.assert_allclose(
            np.asarray(leaf), np.asarray(ref),
            atol=2.0 ** -codec.frac_bits,
        )


def test_repair_packed_report_and_channel_major():
    codec = GradCodec.make(world=2, correct=True)
    g = jnp.asarray(
        np.random.default_rng(7).standard_normal(50).astype(np.float32)
    )
    wire = codec.encode_packed(g, channel_major=True)  # (n+2, B)
    bad = wire.at[0, 3].set(jnp.mod(wire[0, 3] + 9, codec.base.moduli[0]))
    fixed, report = repair_packed(codec, bad, channel_major=True)
    assert report == {"repaired": 1, "unrecoverable": 0}
    np.testing.assert_array_equal(np.asarray(fixed), np.asarray(wire))
    clean, report0 = repair_packed(codec, wire, channel_major=True)
    assert report0 == {"repaired": 0, "unrecoverable": 0}
    np.testing.assert_array_equal(np.asarray(clean), np.asarray(wire))


def test_train_step_rns_repair_fixes_injected_corruption():
    """make_train_step(rns_repair=True) with a corrupting transport hook:
    the injected wire fault is repaired (metric counts it) and the params
    update is BITWISE identical to the uncorrupted run."""
    from repro.configs import get_config
    from repro.models import init_params
    from repro.train.data import SyntheticLM
    from repro.train.optimizer import AdamWConfig, adamw_init
    from repro.train.train_step import make_train_step

    cfg = get_config("mamba2-370m").smoke()
    opt_cfg = AdamWConfig(warmup=2, decay_steps=4)
    params = init_params(cfg, jax.random.key(0))
    batch = jax.tree_util.tree_map(
        jnp.asarray, SyntheticLM(cfg, seq=16, batch=2).batch_at(0)
    )
    codec = GradCodec.make(world=2, correct=True)
    mesh = _mesh1()

    def corrupt(buf):
        return buf.at[0, 0].set(
            jnp.mod(buf[0, 0] + 1, codec.base.moduli[0])
        )

    def run(hook):
        step = make_train_step(cfg, opt_cfg, rns_codec=codec,
                               dp_axis="data", rns_repair=True,
                               transport_hook=hook)
        fn = jax.jit(jax.shard_map(step, mesh=mesh,
                                   in_specs=(P(), P(), P("data")),
                                   out_specs=(P(), P(), P()),
                                   check_vma=False))
        return fn(params, adamw_init(params), batch)

    p_clean, _, m_clean = run(None)
    p_fixed, _, m_fixed = run(corrupt)
    assert int(m_clean["repaired"]) == 0
    assert int(m_fixed["repaired"]) == 1
    assert int(m_fixed["unrepairable"]) == 0
    for a, b in zip(jax.tree_util.tree_leaves(p_clean),
                    jax.tree_util.tree_leaves(p_fixed)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_train_step_rns_repair_requires_correct_codec():
    from repro.configs import get_config
    from repro.train.optimizer import AdamWConfig
    from repro.train.train_step import make_train_step

    cfg = get_config("mamba2-370m").smoke()
    with pytest.raises(ValueError, match="correct=True"):
        make_train_step(cfg, AdamWConfig(), rns_codec=GradCodec.make(world=2),
                        rns_repair=True)


def test_launch_rns_correct_smoke(capsys):
    """launch/train.py --rns-correct finishes a smoke run with one injected
    corruption and logs the repaired step (the acceptance criterion)."""
    from repro.launch.train import main as train_main

    train_main(["--arch", "mamba2-370m", "--steps", "3", "--batch", "2",
                "--seq", "16", "--rns-correct", "--inject-corrupt-step",
                "1"])
    out = capsys.readouterr().out
    assert "[rns-correct] repaired 1" in out
    assert "at step 1" in out
    assert "done" in out
