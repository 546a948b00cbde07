"""Training driver: data pipeline -> jitted train step -> checkpoints.

Runs REAL steps on whatever devices exist (CPU smoke configs by default;
the same code path pjit-shards on a TPU mesh).  Demonstrates the
fault-tolerance loop: resume from the newest repairable checkpoint (RRNS
repair-on-restore, DESIGN.md §14), policy-driven async saves on a single
background writer, and a step-time watchdog (straggler hook).

    PYTHONPATH=src python -m repro.launch.train --arch gemma-2b --smoke \
        --steps 30 --ckpt-dir /tmp/ck --ckpt-policy 2@10,5,60s \
        --ckpt-keep 3 [--rns-allreduce]

    # RRNS locate-and-correct transport with an injected wire corruption
    PYTHONPATH=src python -m repro.launch.train --arch mamba2-370m --smoke \
        --steps 4 --rns-correct --inject-corrupt-step 2

    # corrupt one RRNS channel of the newest checkpoint, then watch the
    # restore repair it in stride (2 channels: refuse + fall back)
    PYTHONPATH=src python -m repro.launch.train --smoke --steps 10 \
        --ckpt-dir /tmp/ck --inject-ckpt-corrupt 1
"""
from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

import repro  # noqa: F401  (x64)
from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_params
from repro.train import checkpointer as ckpt
from repro.train.data import Prefetcher, SyntheticLM
from repro.train.optimizer import AdamWConfig, adamw_init
from repro.train.train_step import make_train_step


def _corrupt_wire(codec):
    """Transport hook that flips one residue of the local wire buffer —
    element 0's channel-0 residue moves by +1 mod m_1, a guaranteed-real,
    still-canonical corruption (the injection half of the --rns-correct
    smoke demo; the repair half must undo it exactly)."""
    m0 = int(codec.base.moduli[0])

    def hook(buf):
        # raw channel-major (n_channels, B) residues of the RnsArray wire
        # buffer (train_step unwraps/rewraps the type around the hook)
        return buf.at[0, 0].set(jnp.mod(buf[0, 0] + 1, m0))

    return hook


def make_dp_step(cfg, opt_cfg, codec=None, *, repair=False, inject=False,
                 devices=None):
    """Data-parallel step over a 'data' axis of ``devices`` (default: all,
    in ``jax.devices()`` order), run under shard_map.

    With ``codec``, the paper's RNS-exact gradient all-reduce, bucketed:
    per-device grads encode (fused Pallas kernel when the codec qualifies)
    into ONE contiguous (n_channels, B_total) int32 buffer, the whole
    pytree moves in a single per-channel psum, and the fused decode runs
    at the optimizer boundary inside ``adamw_update`` (dist/grad_codec.py,
    DESIGN.md §9).  Without one, a plain fp32 pmean of the gradients — the
    baseline the RNS path is compared with.

    repair=True adds the RRNS locate-and-correct pass on the wire buffer
    (needs a ``correct=True`` codec, DESIGN.md §10); inject=True corrupts
    one residue first, so the returned step demonstrates in-flight repair.

    Returns ``(step, mesh)``.  The step donates params and optimizer state
    (the caller rebinds both to its outputs): at published widths the
    codec's int32 channel buffers leave no room for a second copy of
    either on a 16 GiB chip, so place them on ``mesh`` replicated
    (``replicate``) before the first step.
    """
    from jax.sharding import PartitionSpec as P

    from repro.dist.sharding import auto_mesh

    devs = list(jax.devices() if devices is None else devices)
    mesh = auto_mesh((len(devs),), ("data",), devices=devs)
    step = make_train_step(
        cfg, opt_cfg, dp_axis="data", rns_codec=codec, rns_repair=repair,
        transport_hook=_corrupt_wire(codec) if inject else None,
    )
    fn = jax.shard_map(
        step, mesh=mesh,
        in_specs=(P(), P(), P("data")),
        out_specs=(P(), P(), P()),
        check_vma=False,
    )
    return jax.jit(fn, donate_argnums=(0, 1)), mesh


def replicate(tree, mesh):
    """Commit ``tree`` to every device of ``mesh`` (what ``make_dp_step``'s
    step expects of params and optimizer state)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    return jax.device_put(tree, NamedSharding(mesh, P()))


def main(argv=None) -> dict:
    """Run the training loop; returns ``{"params", "losses"}`` (the final
    parameters and the loss of every step this run took)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma-2b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--no-smoke", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--save-every", type=int, default=10)
    ap.add_argument("--ckpt-policy", default="",
                    help="save-policy grammar 'N | N@M | Ns | Nm, ...' "
                         "(e.g. '2@10,5,60s'); overrides --save-every")
    ap.add_argument("--ckpt-keep", type=int, default=0,
                    help="retention GC: keep only the newest K committed "
                         "steps (0 = keep everything)")
    ap.add_argument("--inject-ckpt-corrupt", type=int, default=0,
                    metavar="K",
                    help="corrupt K RRNS channels of the newest saved "
                         "checkpoint before restoring: 1 demonstrates "
                         "locate-and-correct, 2 the refuse-and-fall-back "
                         "path (needs --ckpt-dir)")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--rns-allreduce", action="store_true",
                    help="use the paper's RNS gradient aggregation (DP demo)")
    ap.add_argument("--rns-correct", action="store_true",
                    help="RNS aggregation with the second redundant modulus "
                         "and in-flight RRNS repair of corrupted wire "
                         "buffers (implies --rns-allreduce)")
    ap.add_argument("--inject-corrupt-step", type=int, default=-1,
                    metavar="N",
                    help="with --rns-correct: corrupt one wire residue at "
                         "step N to demonstrate the in-place repair")
    ap.add_argument("--unfused-codec", action="store_true",
                    help="force the jnp encode/decode path for the RNS "
                         "codec (A/B against the fused Pallas kernels)")
    ap.add_argument("--watchdog-x", type=float, default=3.0,
                    help="warn when a step exceeds x * median step time")
    ap.add_argument("--profile-start-step", type=int, default=-1,
                    metavar="N",
                    help="train step at which to start a JAX profiler "
                         "trace (-1 disables; levanter Performance-Guide "
                         "pattern: start step + step count)")
    ap.add_argument("--profile-steps", type=int, default=0, metavar="N",
                    help="train steps to capture in the profiler window")
    ap.add_argument("--profile-dir", default=None, metavar="DIR",
                    help="profiler artifact directory (default: "
                         "--ckpt-dir when set, else '.')")
    args = ap.parse_args(argv)
    if args.inject_corrupt_step >= 0 and not args.rns_correct:
        ap.error("--inject-corrupt-step needs --rns-correct (there is no "
                 "repair path to demonstrate without it)")
    if args.inject_ckpt_corrupt and not args.ckpt_dir:
        ap.error("--inject-ckpt-corrupt needs --ckpt-dir (there is no "
                 "checkpoint to corrupt without one)")

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    cfg.validate()
    opt_cfg = AdamWConfig(warmup=5, decay_steps=max(args.steps, 10))

    params = init_params(cfg, jax.random.key(0))
    opt_state = adamw_init(params)
    start_step = 0

    if args.ckpt_dir:
        if args.inject_ckpt_corrupt:
            latest = ckpt.discover_latest(args.ckpt_dir)
            if latest is None:
                ap.error("--inject-ckpt-corrupt: nothing saved under "
                         f"{args.ckpt_dir} yet")
            ckpt.inject_channel_corruption(
                os.path.join(args.ckpt_dir, f"step_{latest}"),
                leaf=0, channels=tuple(range(args.inject_ckpt_corrupt)),
            )
            print(f"[inject] corrupted {args.inject_ckpt_corrupt} RRNS "
                  f"channel(s) of step {latest}, leaf 0, element 0")
        abs_tree = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
            {"params": params, "opt": opt_state},
        )
        try:
            # restore directly (one scan+read+hash of the checkpoint);
            # probing latest first would read and decode it all twice
            tree, start_step, extra, rep = ckpt.restore(
                args.ckpt_dir, abs_tree)
        except FileNotFoundError:
            pass  # fresh run: nothing restorable yet
        else:
            params, opt_state = tree["params"], tree["opt"]
            print(f"[resume] restored step {start_step}: "
                  f"{rep['leaves']} leaves, "
                  f"repaired_leaves={rep['repaired_leaves']} "
                  f"repaired_elements={rep['repaired_elements']} "
                  f"steps_skipped={rep['steps_skipped']}")
            opt_step = int(np.asarray(opt_state["step"]))
            if opt_step != start_step:
                print(f"[resume] WARNING: optimizer step {opt_step} != "
                      f"checkpoint step {start_step}")

    inject_fn = None
    if args.rns_allreduce or args.rns_correct:
        from repro.dist.grad_codec import GradCodec

        codec = GradCodec.make(world=max(len(jax.devices()), 2),
                               fused=not args.unfused_codec,
                               correct=args.rns_correct)
        step_fn, mesh = make_dp_step(cfg, opt_cfg, codec,
                                     repair=args.rns_correct)
        ndev = mesh.size
        params, opt_state = replicate((params, opt_state), mesh)
        if args.rns_correct and args.inject_corrupt_step >= 0:
            inject_fn, _ = make_dp_step(cfg, opt_cfg, codec,
                                        repair=True, inject=True)
        assert args.batch % ndev == 0, "batch must divide device count"
        reds = "+".join(str(r) for r in codec.redundant)
        print(f"[rns] RNS gradient all-reduce over {ndev} device(s), "
              f"base n={codec.base.n} moduli, redundant {reds}, "
              f"bucketed single-psum transport, "
              f"{'fused Pallas' if codec.use_fused else 'jnp'} codec"
              + (", RRNS locate-and-correct armed" if args.rns_correct
                 else ""))
    else:
        step_fn = jax.jit(
            make_train_step(cfg, opt_cfg, microbatches=args.microbatches)
        )

    loader = SyntheticLM(cfg, seq=args.seq, batch=args.batch)
    prefetch = Prefetcher(loader, start_step=start_step)
    saver = None
    if args.ckpt_dir:
        policy = args.ckpt_policy or str(args.save_every)
        saver = ckpt.Checkpointer(args.ckpt_dir, policy,
                                  keep=args.ckpt_keep or None)
        print(f"[ckpt] policy {policy!r}, "
              f"keep {'all' if not args.ckpt_keep else args.ckpt_keep}, "
              f"async RRNS-coded saves under {args.ckpt_dir}")
    from repro.launch.profiling import ProfilerWindow

    window = ProfilerWindow(
        args.profile_start_step, args.profile_steps,
        args.profile_dir or args.ckpt_dir or ".", label="train",
    )
    times, losses = [], []
    try:
        for _ in range(start_step, args.steps):
            window.step()
            step, batch = prefetch.next()
            t0 = time.time()
            fn = (inject_fn if inject_fn is not None
                  and step == args.inject_corrupt_step else step_fn)
            params, opt_state, metrics = fn(
                params, opt_state,
                jax.tree_util.tree_map(jnp.asarray, batch),
            )
            metrics = {k: float(v) for k, v in metrics.items()}
            dt = time.time() - t0
            times.append(dt)
            losses.append(metrics["loss"])
            med = sorted(times)[len(times) // 2]
            if len(times) > 3 and dt > args.watchdog_x * med:
                print(f"[watchdog] step {step} took {dt:.2f}s "
                      f"(median {med:.2f}s) — straggler suspected")
            if metrics.get("repaired", 0) > 0:
                print(f"[rns-correct] repaired "
                      f"{int(metrics['repaired'])} corrupted wire "
                      f"value(s) in place at step {step} — no rollback")
            if metrics.get("unrepairable", 0) > 0:
                print(f"[rns-correct] step {step}: "
                      f"{int(metrics['unrepairable'])} element(s) beyond "
                      f"single-channel repair — checkpoint rollback advised")
            print(f"step {step:4d} loss={metrics['loss']:.4f} "
                  f"gnorm={metrics['gnorm']:.3f} {dt*1e3:.0f}ms")
            if saver is not None:
                saver.maybe_save(step + 1,
                                 {"params": params, "opt": opt_state},
                                 extra={"opt_step": int(metrics["opt_step"])})
    finally:
        window.close()
        prefetch.close()
        if saver is not None:
            saver.close()  # drain the queue; re-raise any failed save
    if window.enabled and window.artifact:
        print(f"[profile] captured {window.captured} step(s) under "
              f"{window.artifact}")
    print("done")
    return {"params": params, "losses": losses}


if __name__ == "__main__":
    main()
