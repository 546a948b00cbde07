"""What every cell shares: finding its files by name, the device check,
the compile cache, the window's arithmetic and the result line.

A cell is one entry of ``workloads`` in ``BENCHMARK.json``.  Its files are
found by name, so a later cell, configuration, traffic mix or per-layer
metric is added with files and entries alone:

* ``bench/configs/<config>.json``: the configuration as it runs;
* ``bench/families/<model_type>.py``: ``model_config(spec)``, its
  published keys translated into the program's ``ModelConfig``;
* ``bench/traffic/<traffic>.json``: the mix, read by ``gen_traffic``;
* ``bench/metrics/<metric>.py``, or ``<family>.py`` for a metric named
  ``<family>.<rest>``: a reader ``read(run) -> float | None``;
* ``bench/reference/<config>.py``: the plain reference;
* ``bench/work/<config>.py``: operations and bytes from the shapes;
* ``bench/peaks.json``: the chip's peaks by ``device_kind``.
"""
from __future__ import annotations

import importlib.util
import json
import math
import os
import sys
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
SPEC_FILE = ROOT / "BENCHMARK.json"
CACHE_DIR = ROOT / ".jax_cache"


class SetupError(RuntimeError):
    """The run cannot produce a result (no chip, missing files)."""


# --------------------------------------------------------------- discovery
def load_spec(path: Path | None = None) -> dict:
    path = path or SPEC_FILE
    if not path.is_file():
        raise SetupError(f"{path} not found")
    return json.loads(path.read_text())


def cell(spec: dict, name: str) -> dict:
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise SetupError(f"no workload {name!r} in BENCHMARK.json; cells: "
                     f"{[w['name'] for w in spec['workloads']]}")


def data_file(kind: str, name: str, bench: Path | None = None) -> dict:
    path = (bench or BENCH) / kind / f"{name}.json"
    if not path.is_file():
        raise SetupError(f"{path} not found")
    return json.loads(path.read_text())


def module(kind: str, name: str, bench: Path | None = None):
    """The module ``bench/<kind>/<name>.py`` (names may hold dots)."""
    path = (bench or BENCH) / kind / f"{name}.py"
    if not path.is_file():
        raise SetupError(f"{path} not found")
    mod_name = f"bench_{kind}_{name}".replace(".", "_").replace("-", "_")
    if mod_name in sys.modules and sys.modules[mod_name].__file__ == str(path):
        return sys.modules[mod_name]
    s = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(s)
    sys.modules[mod_name] = mod
    s.loader.exec_module(mod)
    return mod


def metric_reader(name: str, bench: Path | None = None):
    """The reader of per-layer metric ``name``: ``bench/metrics/<name>.py``,
    else the one of its family, ``bench/metrics/<family>.py``, where the
    family is the part of the name before the first dot."""
    if ((bench or BENCH) / "metrics" / f"{name}.py").is_file():
        return module("metrics", name, bench)
    return module("metrics", name.split(".")[0], bench)


def cell_metrics(spec: dict, name: str, trace: bool) -> list[dict]:
    """The metrics a run of cell ``name`` reports: its end-to-end metrics
    with ``--trace 0``, its per-layer metrics with ``--trace 1``."""
    group = spec["per_layer" if trace else "end_to_end"]
    return [m for m in group if name in m.get("workloads", [name])]


def peaks(kind: str, bench: Path | None = None) -> dict:
    table = json.loads(((bench or BENCH) / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise SetupError(f"no peaks for device_kind {kind!r} in "
                         f"bench/peaks.json; known: {list(table['devices'])}")
    return table["devices"][kind]


# ------------------------------------------------------------------ device
def require_chips(n: int) -> dict:
    """The device record of the result line; a run with no TPU, or fewer
    chips than the cell asks for, stops here."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SetupError(f"no TPU: JAX found {devs[0].platform} devices; "
                         f"this benchmark measures the chip only")
    if len(devs) < n:
        raise SetupError(f"the cell needs {n} chips, JAX found {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": n}


def enable_cache() -> str:
    """JAX's persistent cache at the checkout's fixed ``.jax_cache``; every
    program is kept, so only a checkout's first run compiles."""
    import jax

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return str(CACHE_DIR)


def memory_peak(devices) -> int:
    peaks_ = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in devices]
    return int(max(peaks_))


# ------------------------------------------------------ window arithmetic
def percentile(xs, q: float) -> float:
    """The q-th percentile of every sample (numpy's linear rule)."""
    if len(xs) == 0:
        return float("nan")
    return float(np.percentile(np.asarray(xs, np.float64), q))


def ttft_samples(reqs, t_close: float) -> list[float]:
    """Time to first token of every request due in the window (from 0 to
    the close), from its due time; one with no first token by the close
    counts its age."""
    out = []
    for r in reqs:
        if not 0 <= r["due"] < t_close:
            continue
        first = r["times"][0] if r["times"] else None
        end = first if first is not None and first <= t_close else t_close
        out.append(end - r["due"])
    return out


def itl_samples(reqs, t0: float, t_close: float) -> list[float]:
    """Every gap between consecutive output tokens of every stream that
    ends inside the window."""
    out = []
    for r in reqs:
        ts = r["times"]
        for a, b in zip(ts, ts[1:]):
            if b <= t_close and b > t0:
                out.append(b - a)
    return out


def tokens_in(reqs, t0: float, t_close: float) -> int:
    return sum(1 for r in reqs for t in r["times"] if t0 < t <= t_close)


# -------------------------------------------------------------- the result
def result_line(*, correct: bool, attempted: int, failed: int,
                metrics: dict, device: dict, checks: list,
                breakdown: dict | None = None) -> str:
    """The run's last line of standard output; ``checks`` (name, number,
    limit, ok) come last, under a key of their own."""
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {c[0]: {"value": c[1], "limit": c[2], "ok": bool(c[3])}
                     for c in checks}
    return json.dumps(out, allow_nan=False)


def print_checks(checks: list) -> None:
    """The numbers compared, each beside its limit: the last lines on
    standard error."""
    for name, value, limit, ok in checks:
        print(f"check {name}: {value!r} limit {limit!r} "
              f"{'ok' if ok else 'FAILED'}", file=sys.stderr, flush=True)


def finite(x) -> bool:
    return x is not None and isinstance(x, (int, float)) and math.isfinite(x)
