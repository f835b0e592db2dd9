"""Shared plumbing of the chip benchmark: find a cell's files by name, set
up the compile cache, gate on the device, run the per-layer reducers and
print the result line.

Everything a cell is made of lives in a file of its own, found by name:

    bench/cells/<cell>.json        configuration, traffic mix, engine
                                   settings, fixed rate, correctness limit
    bench/configs/<config>.json    published sizes, ``reduced``, ``assumed``
    bench/configs/<config>.ref.py  plain float32 reference and its weights
    bench/traffic/<mix>.json       parameters for ``gen.py``
    bench/metrics/<metric>.py      ``reduce(rec) -> float | None``
    bench/work/<kernel>.py         operations and bytes from shapes

``BENCHMARK.json`` at the checkout root lists which metrics each cell
reports, so adding a cell or a metric is adding files and entries.
"""

from __future__ import annotations

import importlib.util
import json
import math
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".bench_cache" / "jax"


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str | None = None):
    """Import a file by path (names may hold dots and dashes)."""
    name = name or "bench_" + "".join(
        c if c.isalnum() else "_" for c in str(Path(path).resolve()))
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def benchmark_doc(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def cell_files(workload: str, bench: Path = BENCH) -> dict:
    """The cell, its configuration, reference and traffic mix, by name."""
    cell = load_json(bench / "cells" / f"{workload}.json")
    cfg = load_json(bench / "configs" / f"{cell['config']}.json")
    return {
        "name": workload,
        "cell": cell,
        "config": cfg,
        "traffic": load_json(bench / "traffic" / f"{cell['traffic']}.json"),
        "ref": load_module(bench / "configs" / f"{cell['config']}.ref.py"),
    }


def metrics_for(doc: dict, workload: str, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
    return [m for m in doc[kind]
            if workload in m.get("workloads", [workload])]


def reducer(name: str, bench: Path = BENCH):
    return load_module(bench / "metrics" / f"{name}.py").reduce


def reduce_metrics(entries: list[dict], rec: dict,
                   bench: Path = BENCH) -> dict:
    """Run each metric's reducer; a reducer that finds nothing to read
    returns None and the metric is left out of the line."""
    out = {}
    for m in entries:
        v = reducer(m["name"], bench)(rec)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def work(name: str, bench: Path = BENCH):
    """A kernel's or model's operation and byte counts, from shapes."""
    return load_module(bench / "work" / f"{name}.py")


def peaks(kind: str, bench: Path = BENCH) -> dict:
    table = load_json(bench / "peaks.json")
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


# ------------------------------------------------------------ statistics

def percentile(values, q: float) -> float | None:
    """Nearest-rank percentile (q in [0, 100]) over all samples."""
    if not values:
        return None
    s = sorted(values)
    k = max(0, math.ceil(q / 100.0 * len(s)) - 1)
    return float(s[k])


# ------------------------------------------------------------ jax set-up

def enable_compile_cache(cache_dir: Path = CACHE_DIR) -> None:
    """JAX's persistent compile cache at a fixed path in the checkout; every
    program is written, eager per-op compiles included."""
    import jax

    cache_dir.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(cache_dir))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def device_gate(chips: int) -> dict:
    """Refuse anything but a TPU with at least ``chips`` devices."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise SystemExit(
            f"benchmark needs {chips} TPU chip(s); JAX found "
            f"{len(devs)} {devs[0].platform} device(s)")
    return device_info(chips)


def device_info(chips: int) -> dict:
    import jax

    devs = jax.devices()[:chips]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes(chips: int) -> int | None:
    import jax

    peaks_ = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks_.append(int(stats["peak_bytes_in_use"]))
    return max(peaks_) if peaks_ else None


class CompileCounter:
    """Counts JAX lowerings and backend compiles while ``active``."""

    EVENTS = {
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "lowerings",
        "/jax/core/compile/backend_compile_duration": "backend_compiles",
    }

    def __init__(self):
        from jax._src import monitoring

        self.active = False
        self.counts = {v: 0 for v in self.EVENTS.values()}
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if self.active and event in self.EVENTS:
            self.counts[self.EVENTS[event]] += 1


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def emit(result: dict, checks: dict) -> None:
    """The numbers compared, each beside its limit, as the last lines of
    standard error; then the result as the last line of standard output,
    with the same numbers under ``checks``, its last key."""
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} limit {c['limit']!r} "
            f"({'ok' if c['ok'] else 'FAILED'})")
    result = dict(result)
    result["checks"] = checks
    sys.stderr.flush()
    print(json.dumps(result), flush=True)

