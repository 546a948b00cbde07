"""Compile for a described TPU v5e, with no chip attached.

The seven Pallas kernels at the widths ``chip_smoke.py`` drives them with
must lower through Mosaic (each compiled program holds a
``tpu_custom_call``), and the full-width ``llama3.2-3b`` paged decode and
bucketed extend steps must fit one chip's 16 GiB.  ``import repro`` turns
x64 on, so these compiles also guard the kernels' int32/f32 discipline.

The topology is described inside a fixture, never at import: only one
process may hold the TPU library, and every test worker imports this file.
"""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import repro  # noqa: F401  (x64 on, as in every entry point)

HBM_BYTES = 16 << 30
SLOTS, CACHE_LEN, PAGE = 4, 1024, 16  # chip_smoke.py's serving settings


@pytest.fixture(scope="module")
def one_chip():
    """One described v5e chip, with the persistent compile cache off: a
    compile for a chip that is not attached cannot be read back here."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler installed here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _kernel_lowerings(sh):
    """name -> zero-arg lowering of each ``*_kernel_call`` at chip widths."""
    from repro.core import make_base
    from repro.dist.grad_codec import GradCodec
    from repro.kernels.codec_decode import codec_decode_kernel_call
    from repro.kernels.codec_encode import codec_encode_kernel_call
    from repro.kernels.modmul import modmul_kernel_call
    from repro.kernels.mont_ladder import (
        mont_ladder_kernel_call, mont_mul_kernel_call,
    )
    from repro.kernels.mrc import mrc_kernel_call
    from repro.kernels.rns_compare import compare_kernel_call

    def S(*shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sh)

    n, B = 8, 4096
    base = make_base(n, bits=15)
    codec = GradCodec.make(world=4)
    c = codec.base.n
    nl, nh, nch, Bm = 8, 8, 9, 2048  # CryptoContext(n_limbs=8), BASE_MA
    tables = [S(nl, nl), S(nch, 1), S(nh, nl), S(nh, nh), S(nh, 1),
              S(nch, nh), S(nh, 1)]
    return {
        "mrc": lambda: mrc_kernel_call.lower(
            S(n, B), S(n, n), S(n, 1), interpret=False),
        "modmul": lambda: modmul_kernel_call.lower(
            S(n + 1, B), S(n + 1, B), S(n + 1, 1), interpret=False),
        "compare": lambda: compare_kernel_call.lower(
            S(n, B), S(1, B), S(n, B), S(1, B), S(n, n), S(n, 1), S(n, 1),
            ma=base.ma, interpret=False),
        "codec_encode": lambda: codec_encode_kernel_call.lower(
            S(1, B, dt=jnp.float32), S(c + 1, 1), S(c + 1, 1), S(c + 1, 1),
            scale=float(1 << codec.frac_bits), qh=codec.qmax >> 15,
            ql=codec.qmax & 0x7FFF, interpret=False),
        "codec_decode": lambda: codec_decode_kernel_call.lower(
            S(c + 1, B), S(c, c), S(c, 1), S(6, 1), n=c,
            inv_scale=2.0 ** -codec.frac_bits, interpret=False),
        "mont_mul": lambda: mont_mul_kernel_call.lower(
            S(nch, Bm), S(nh, Bm), S(nch, Bm), S(nh, Bm), S(nl, Bm),
            S(nh, Bm), *tables, interpret=False),
        "mont_ladder": lambda: mont_ladder_kernel_call.lower(
            S(nch, Bm), S(nh, Bm), S(nch, Bm), S(nh, Bm), S(1, Bm),
            S(nl, Bm), S(nh, Bm), *tables, interpret=False),
    }


KERNELS = ("mrc", "modmul", "compare", "codec_encode", "codec_decode",
           "mont_mul", "mont_ladder")


@pytest.mark.parametrize("name", KERNELS)
def test_kernel_compiles_for_v5e(one_chip, name):
    compiled = _kernel_lowerings(one_chip)[name]().compile()
    assert "tpu_custom_call" in compiled.as_text()


def _serving_step(sh, which):
    """Lowering of the engine's paged decode step, or of its widest
    bucketed extend, for full-width llama3.2-3b on one described chip."""
    from repro.launch.serve import serving_config
    from repro.models import abstract_params, decode_step, extend_step
    from repro.serve.serve_step import paged_pool_abstract

    cfg = serving_config("llama3.2-3b", smoke=False)
    params = abstract_params(cfg)
    pool = paged_pool_abstract(cfg, params, 1 + SLOTS * CACHE_LEN // PAGE,
                               PAGE)

    def place(tree):
        return jax.tree_util.tree_map(
            lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=sh),
            tree)

    def S(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sh)

    n_pg = CACHE_LEN // PAGE
    if which == "decode":
        def step(p, c, t, pos, pages):
            logits, c = decode_step(cfg, p, c, t, pos, pages=pages,
                                    page_size=PAGE)
            return jnp.argmax(logits, axis=-1).astype(jnp.int32), c

        args = (S(SLOTS, 1), S(SLOTS), S(SLOTS, n_pg))
    else:
        def step(p, c, t, pos, idx, pages, valid, scratch):
            return extend_step(cfg, p, c, t, pos, logit_index=idx,
                               pages=pages, page_size=PAGE, valid_len=valid,
                               scratch=scratch)

        args = (S(1, CACHE_LEN), S(), S(), S(1, n_pg), S(), S())
    return jax.jit(step).lower(place(params), place(pool), *args)


@pytest.mark.parametrize("which", ("decode", "extend"))
def test_full_width_step_fits_one_v5e(one_chip, which):
    compiled = _serving_step(one_chip, which).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total <= HBM_BYTES, f"{which}: {total / 2**30:.2f} GiB"
    # the weights alone: bf16 llama3.2-3b is ~6.4 GB (f32 would not fit)
    assert mem.argument_size_in_bytes > 6 << 30
    assert not re.search(r"= f64\[", compiled.as_text())


@pytest.mark.parametrize("preset,E,K,cf", (
    ("qwen2-moe-a2.7b", 60, 4, 15.0), ("moonshot-v1-16b-a3b", 64, 6, 11.0)))
def test_grouped_expert_layer_compiles_for_v5e(one_chip, preset, E, K, cf):
    """One expert layer at published widths and the widest bucket (4096
    tokens) at the benchmark's dropless capacity runs the grouped form:
    XLA's ragged-dot lowers to a Mosaic kernel, and the program holds
    less than one (E, C, d) expert buffer of the buffer form."""
    import dataclasses

    from repro.configs import get_config
    from repro.models.moe import capacity, dispatch_form, init_moe, moe_forward

    cfg = dataclasses.replace(get_config(preset), n_experts=E, top_k=K,
                              capacity_factor=cf)
    s, d = 4096, cfg.d_model
    assert dispatch_form(cfg, s) == "grouped"
    p = jax.eval_shape(lambda: init_moe(jax.random.key(0), cfg, jnp.bfloat16))
    p = jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=one_chip), p)
    x = jax.ShapeDtypeStruct((1, s, d), jnp.bfloat16, sharding=one_chip)
    compiled = jax.jit(lambda p, x: moe_forward(p, cfg, x)).lower(
        p, x).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "ragged-dot" in text
    assert not re.search(r"= f64\[", text)
    buffer_bytes = E * capacity(cfg, s) * d * 2
    assert compiled.memory_analysis().temp_size_in_bytes < buffer_bytes
