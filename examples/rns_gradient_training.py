"""Train a small LM with the paper's RNS-exact gradient aggregation and
verify the loss trajectory matches plain fp32 all-reduce.

The gradients are quantized to fixed point, encoded into residue channels,
psum'd per channel (exact ring homomorphism), and decoded — with sign and
clip decisions available through Algorithm-1 comparisons WITHOUT
reconstruction (repro/dist/grad_codec.py).

    PYTHONPATH=src python examples/rns_gradient_training.py
"""
import numpy as np

import jax
import jax.numpy as jnp

import repro  # noqa: F401
from repro.configs import get_config
from repro.dist.grad_codec import GradCodec
from repro.launch.train import make_dp_step
from repro.models import init_params
from repro.train.data import SyntheticLM
from repro.train.optimizer import AdamWConfig, adamw_init
from repro.train.train_step import make_train_step

STEPS = 45
cfg = get_config("llama3.2-3b").smoke()
opt_cfg = AdamWConfig(lr=1e-3, warmup=5, decay_steps=STEPS, weight_decay=0.0)
codec = GradCodec.make(world=8)
print(f"codec: {codec.base.n}+1 channels of 15-bit moduli, "
      f"M ~ 2^{codec.base.M.bit_length()}, quant step 2^-{codec.frac_bits}")

rns_step, _ = make_dp_step(cfg, opt_cfg, codec)
fp_step = jax.jit(make_train_step(cfg, opt_cfg))
loader = SyntheticLM(cfg, seq=32, batch=8, pattern="arith")


def run(step_fn):
    params = init_params(cfg, jax.random.key(0))
    opt = adamw_init(params)
    losses = []
    for s in range(STEPS):
        batch = jax.tree_util.tree_map(jnp.asarray, loader.batch_at(s))
        params, opt, m = step_fn(params, opt, batch)
        losses.append(float(m["loss"]))
    return losses


l_rns = run(rns_step)
l_fp = run(fp_step)
print(f"{'step':>4} {'rns_loss':>9} {'fp32_loss':>9}")
for i in range(0, STEPS, 4):
    print(f"{i:4d} {l_rns[i]:9.4f} {l_fp[i]:9.4f}")
drift = max(abs(a - b) for a, b in zip(l_rns, l_fp))
print(f"max |loss drift| over {STEPS} steps: {drift:.4f}")
assert drift < 0.05, "RNS aggregation diverged from fp32"
assert l_rns[-1] < l_rns[0] - 1.0, "did not learn"
print("RNS-aggregated training matches fp32 and learns ✓")
