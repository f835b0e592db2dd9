#!/usr/bin/env python3
"""Knee sweep of an LM cell: the same engine under open-loop windows at
each given rate, one after another in one process (set-up is paid once).

    python3 bench/knee.py --workload <cell> --rates 3,5,7 --seconds 20 --seed 1

One JSON line per rate: requests sent and finished, tokens/s inside the
window, TTFT and inter-token p50/p95, and the backlog (requests waiting
for admission) at the window's quarter points with its growth in
requests per second over the second half. The knee is the highest rate
whose backlog does not grow. Rates found here go into the cell's file.
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
import lm_serve  # noqa: E402


def backlog(win: dict) -> dict:
    q = win["queue"]
    t0, sec = win["t0"], win["seconds"]

    def at(f):
        ws = [n for t, n in q if t <= t0 + f * sec]
        return ws[-1] if ws else 0

    quarters = [at(f) for f in (0.25, 0.5, 0.75, 1.0)]
    return {"waiting_at_quarters": quarters,
            "growth_rps": (quarters[3] - quarters[1]) / (0.5 * sec)}


def summary(win: dict, rate: float) -> dict:
    reqs = win["requests"]
    ttft = [r["times"][0] - r["due"] for r in reqs if r["times"]]
    itl = [b - a for r in reqs for a, b in zip(r["times"], r["times"][1:])]
    toks = sum(1 for r in reqs for t in r["times"] if t <= win["end"])
    pct = harness.percentile
    return {"rate_rps": rate, "sent": len(reqs),
            "finished": sum(r["done"] for r in reqs),
            "tokens_per_s": toks / win["seconds"],
            "ttft_ms": [1e3 * (pct(ttft, 50) or 0), 1e3 * (pct(ttft, 95) or 0)],
            "itl_ms": [1e3 * (pct(itl, 50) or 0), 1e3 * (pct(itl, 95) or 0)],
            "drain_s": win["t_last"] - win["end"], **backlog(win)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    files = harness.cell_files(args.workload)
    device = harness.device_gate(files["cell"]["chips"])
    harness.enable_compile_cache()
    t = time.perf_counter()
    system = lm_serve.build(files, args.seed)
    harness.log(f"{device} setup {time.perf_counter() - t:.1f} s")
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        reqs = lm_serve.schedule(files, args.seed + k, args.seconds, rate)
        win = lm_serve.serve_window(system.engine, reqs, args.seconds)
        print(json.dumps(summary(win, rate)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
