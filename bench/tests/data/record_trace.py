"""Record the small v5e trace that ``test_trace_reduce.py`` reads.

Run on one v5e chip from the checkout's root:

    python3 bench/tests/data/record_trace.py

It writes ``.bench_trace/probe/plugins/profile/<time>/*.xplane.pb``;
copy that file to ``bench/tests/data/v5e_trace.xplane.pb``.  Three
rounds of: a 2048x2048 bf16 matmul step inside a ``bench.decode`` span, a
small elementwise step inside a ``bench.admit`` span, a 2 ms sleep.
"""
import glob
import shutil
import time

import jax
import jax.numpy as jnp

OUT = ".bench_trace/probe"


@jax.jit
def matmul_step(x):
    return (x @ x) * 0.5


@jax.jit
def small_step(x):
    return jnp.tanh(x) + 1.0


def main():
    print("devices", jax.devices(), jax.devices()[0].device_kind)
    shutil.rmtree(OUT, ignore_errors=True)
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    matmul_step(x).block_until_ready()
    small_step(x).block_until_ready()
    jax.profiler.start_trace(OUT)
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench.decode"):
            matmul_step(x).block_until_ready()
        with jax.profiler.TraceAnnotation("bench.admit"):
            small_step(x).block_until_ready()
        time.sleep(0.002)
    jax.profiler.stop_trace()
    print(glob.glob(OUT + "/plugins/profile/*/*.xplane.pb"))
    print("memory", jax.devices()[0].memory_stats())


if __name__ == "__main__":
    main()
