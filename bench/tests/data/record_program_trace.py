"""Record the small v5e trace that ``test_program_spans.py`` reads.

Run on one v5e chip from the checkout's root:

    PYTHONPATH=src python3 bench/tests/data/record_program_trace.py

It writes ``.bench_trace/program_probe/plugins/profile/<time>/*.xplane.pb``;
copy that file to ``bench/tests/data/v5e_program_trace.xplane.pb``.  A
tiny paged engine (the ``gemma-2b`` smoke model, 2 slots, pages of 8,
6 pages so that admissions evict, ``rns_verify`` on) serves five
requests once to compile every program, then five more under the
profiler, with the Python tracer off and the HLO protos dropped, which
keeps the file small: every ``serve.*`` span appears.
"""
import glob
import shutil

import jax

import repro  # noqa: F401
from repro.configs import get_config
from repro.models import init_params
from repro.serve.batcher import ContinuousBatcher
from repro.serve.scheduler import Request

OUT = ".bench_trace/program_probe"


def serve(eng, rid0):
    for i in range(5):
        eng.submit(Request(rid=rid0 + i, max_new=6,
                           prompt=[rid0 + i * 3 + 2] * (6 + 3 * i)))
    eng.run_to_completion()
    return eng.drain_completed()


def main():
    print("devices", jax.devices(), jax.devices()[0].device_kind)
    shutil.rmtree(OUT, ignore_errors=True)
    cfg = get_config("gemma-2b").smoke()
    eng = ContinuousBatcher(cfg, init_params(cfg, jax.random.key(0)),
                            n_slots=2, cache_len=32, prefill_chunk=8,
                            prefill_buckets=(8, 16, 32), rns_verify=True,
                            page_size=8, n_pages=6)
    serve(eng, 0)
    # no Python tracer: the file keeps the TraceMe events (the spans, the
    # jit dispatches) and the device's operations
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(OUT, profiler_options=opts)
    done = serve(eng, 100)
    jax.profiler.stop_trace()
    print("served", [(r.rid, len(r.out)) for r in done])
    print("pages", eng.page_stats())
    for path in glob.glob(OUT + "/plugins/profile/*/*.xplane.pb"):
        drop_hlo_protos(path)
        print(path)


def drop_hlo_protos(path):
    """Rewrite the trace at ``path`` without the HLO protos that the
    profiler keeps in ``/host:metadata`` (4.5 MB of the 5.7 MB here; no
    reader of the benchmark reads them)."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    for plane in space.planes:
        if plane.name == "/host:metadata":
            plane.ClearField("event_metadata")
    with open(path, "wb") as f:
        f.write(space.SerializeToString())


if __name__ == "__main__":
    main()
