#!/usr/bin/env python3
"""Readings that set a cell's correctness limit, on the chip at the cell's
own size and load: for each seed, a short window of the served system,
then the widest output gap against the float32 reference (the program's
reading) and the same gap for what the float8 control puts first (the
control's reading).

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 12

One JSON line per seed. The benchmark's own runs never run the control.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import harness  # noqa: E402
import run  # noqa: E402


def readings(files: dict, seed: int, seconds: float) -> dict:
    sysmod = run.systems()[files["config"]["system"]]
    system = sysmod.build(files, seed, seconds)
    win = sysmod.window(system, seconds)
    out = sysmod.outputs(system, win)
    sysmod.free(system)
    t = time.perf_counter()
    sound = sysmod.output_gaps(system, out)
    ctrl = sysmod.output_gaps(system, out, control=True)
    return {"seed": seed, "compared": int(len(sound)),
            "program": float(sound.max()), "control": float(ctrl.max()),
            "program_p99": float(sorted(sound)[int(0.99 * len(sound))]),
            "failed": win["failed"], "reference_s": time.perf_counter() - t}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=12.0)
    args = ap.parse_args(argv)
    files = harness.cell_files(args.workload)
    harness.device_gate(files["cell"]["chips"])
    harness.enable_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(readings(files, seed, args.seconds)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
