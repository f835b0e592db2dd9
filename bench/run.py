#!/usr/bin/env python3
"""Run one benchmark cell once on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (weights from the seed, conversion, compilation, warm-up) is timed
as ``setup_s``; then the window runs for ``--seconds``; then the program's
state is freed and the served outputs are compared with the plain
reference. With ``--trace 0`` the result carries the cell's end-to-end
metrics; with ``--trace 1`` the window runs under the profiler and the
result carries the per-layer metrics, ``busy_s``/``window_s`` and a
breakdown. The last line of standard output is one JSON object. Exits
non-zero, printing no result, without a TPU holding the chips the cell
asks for.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import harness  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def systems():
    import lm_serve
    import vision_stream

    return {"lm_paged": lm_serve, "vision_stream": vision_stream}


def run(args, bench: Path = BENCH, gate: bool = True, t_start=T_START,
        hooks=None) -> tuple[dict, dict]:
    """One run of one cell. Returns (result, checks). ``gate=False`` skips
    the look for a chip and the compile cache (tests drive the rest of a
    run on the CPU); ``hooks`` lets a test break the timed path
    underneath."""
    doc = harness.benchmark_doc(bench.parent)
    files = harness.cell_files(args.workload, bench)
    chips = files["cell"]["chips"]
    device = (harness.device_gate(chips) if gate
              else harness.device_info(chips))
    if gate:
        harness.enable_compile_cache()
    sysmod = systems()[files["config"]["system"]]
    counter = harness.CompileCounter()

    system = sysmod.build(files, args.seed, args.seconds)
    if hooks:
        hooks(system)
    setup_s = time.perf_counter() - t_start
    phases = " ".join(f"{k}={v:.3f}" for k, v in system.phases.items())
    harness.log(f"setup {setup_s:.3f} s: {phases}")

    tracer = None
    if args.trace:
        tracer = Tracing(bench.parent / ".bench_cache" / "trace")
        tracer.start()
    counter.active = True
    win = sysmod.window(system, args.seconds,
                        annotate=tracer.annotate if tracer else None)
    counter.active = False
    if tracer:
        t = time.perf_counter()
        tracer.stop()
        harness.log(f"trace written in {time.perf_counter() - t:.1f} s")
    harness.log(f"compiles inside the window: {counter.counts}")
    rec = {"setup_s": setup_s, "device": device, "cell": files["cell"],
           "config": files["config"], **win,
           **sysmod.program_records(system, win)}
    device["memory_peak_bytes"] = harness.memory_peak_bytes(chips)
    if gate:
        rec["peaks"] = harness.peaks(device["kind"], bench)
    outputs = sysmod.outputs(system, win)
    sysmod.free(system)
    t = time.perf_counter()
    checks = sysmod.check(system, outputs, win)
    harness.log(f"reference check {time.perf_counter() - t:.3f} s")

    result = {"correct": all(c["ok"] for c in checks.values()),
              "attempted": win["attempted"], "failed": win["failed"],
              "metrics": {}, "device": device}
    if tracer:
        t = time.perf_counter()
        tr = tracer.reduce()
        harness.log(f"trace read: {len(tr.ops)} device ops in "
                    f"{time.perf_counter() - t:.1f} s")
        rec["trace"] = tr
        lo, hi = tr.window
        device["busy_s"] = sum(tr.busy_by_device) / len(tr.busy_by_device) / 1e9
        device["window_s"] = (hi - lo) / 1e9
        result["metrics"] = harness.reduce_metrics(
            harness.metrics_for(doc, args.workload, "per_layer"), rec, bench)
        from devtrace import breakdown

        result["breakdown"] = breakdown(tr)
    else:
        result["metrics"] = harness.reduce_metrics(
            harness.metrics_for(doc, args.workload, "end_to_end"), rec, bench)
    return result, checks


class Tracing:
    """The profiler around the window, with the harness's host spans."""

    def __init__(self, log_dir: Path):
        import shutil

        shutil.rmtree(log_dir, ignore_errors=True)
        self.log_dir = log_dir

    def start(self):
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(self.log_dir), profiler_options=opts)

    def annotate(self, name: str):
        import jax

        return jax.profiler.TraceAnnotation(name)

    def stop(self):
        import jax

        jax.profiler.stop_trace()

    def reduce(self):
        import devtrace as tr_mod

        return tr_mod.load(tr_mod.find_xplane(self.log_dir))


def main(argv=None) -> int:
    args = parse(argv)
    result, checks = run(args)
    harness.emit(result, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
