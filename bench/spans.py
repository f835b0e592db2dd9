"""The program's own spans in a traced run: the phases the engines mark
with ``Obs.span`` (``engine.*``, ``vision.*``), read from the profile the
harness wrote around the window, on the device ops' clock.

The first reader logs, as one JSON line on standard error, the device's
idle time by the span that held it (split among the program spans inside
each gap that the harness's step or stream span held) and the slowest
step of each kind with its phases. A program without these spans leaves the
readers nothing to read: they return None.
"""

from __future__ import annotations

import json
import time

import devtrace
import harness

STEPS = ("engine.prefill", "engine.chunk", "engine.decode", "vision.frame")
PHASES = ("engine.schedule", "engine.inputs", "engine.dispatch",
          "engine.sync", "engine.emit", "vision.dispatch", "vision.sync",
          "vision.emit")
# the harness's spans around the engines' work, which program spans refine
WORK = ("engine.step", "stream")
TRACE_DIR = harness.ROOT / ".bench_cache" / "trace"


def read(pd) -> dict:
    """Program span name -> its spans [(start, end)] in ns, from the
    host planes of a ``ProfileData``."""
    names = set(STEPS + PHASES)
    out: dict = {}
    for plane in pd.planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in names:
                    s = int(ev.start_ns)
                    out.setdefault(ev.name, []).append(
                        (s, s + int(ev.duration_ns)))
    return {k: devtrace.Spans(v) for k, v in out.items()}


def program_spans(rec: dict) -> dict | None:
    """The program spans of a traced run, read from its profile once and
    kept in the record; None without a trace. Where there are any, the
    idle split and the slowest steps go to standard error."""
    if "program_spans" not in rec:
        spans = None
        tr = rec.get("trace")
        if tr is not None:
            from jax.profiler import ProfileData

            t = time.perf_counter()
            spans = read(ProfileData.from_file(
                str(devtrace.find_xplane(TRACE_DIR))))
            harness.log(f"program spans read in "
                        f"{time.perf_counter() - t:.1f} s")
            if spans and tr.window is not None:
                harness.log("program spans: " + json.dumps(
                    {**idle_split(tr, spans), "slowest": slowest(tr, spans)}))
        rec["program_spans"] = spans
    return rec["program_spans"]


def host_ms(rec: dict, step: str, sync: str) -> float | None:
    """Mean over the window's ``step`` spans of their length less that of
    the ``sync`` spans inside them: the host's own time in a step."""
    spans = program_spans(rec)
    tr = rec.get("trace")
    if not spans or step not in spans or tr.window is None:
        return None
    lo, hi = tr.window
    steps = [(s, e) for s, e in spans[step] if lo <= s < hi]
    if not steps:
        return None
    syncs = spans.get(sync, devtrace.Spans([]))
    own = sum(e - s - devtrace.overlap_ns(syncs, s, e) for s, e in steps)
    return own / len(steps) / 1e6


def split_gap(tr, spans: dict, s: int, e: int) -> dict:
    """A device idle gap's ns by the span that held them. Where the
    harness's span that covers most of the gap is one around the engine's
    work, each phase gets its overlap with the gap, the steps' own time
    outside their phases goes to the step that covers most of the gap,
    and the rest keeps the harness's name; else the whole gap keeps it."""
    owner = devtrace.gap_owner(tr, s, e)
    if owner not in WORK:
        return {owner: e - s}
    out: dict = {}
    for n in PHASES:
        ov = devtrace.overlap_ns(spans.get(n, ()), s, e)
        if ov:
            out[n] = ov
    phased = sum(out.values())
    best, step, stepped = 0, None, 0
    for n in STEPS:
        ov = devtrace.overlap_ns(spans.get(n, ()), s, e)
        stepped += ov
        if ov > best:
            best, step = ov, n
    if stepped > phased:
        out[step] = stepped - phased
    if e - s > stepped:
        out[owner] = e - s - stepped
    return out


def idle_split(tr, spans: dict) -> dict:
    """Idle seconds in the window by the span that held them, and the
    share of the idle time under the harness's work spans that a program
    span names."""
    lo, hi = tr.window
    by: dict = {}
    for s, e in devtrace.idle_gaps(tr, lo, hi):
        for k, v in split_gap(tr, spans, s, e).items():
            by[k] = by.get(k, 0) + v
    named = sum(v for k, v in by.items() if k in STEPS + PHASES)
    work = named + sum(by.get(k, 0) for k in WORK)
    return {"idle_gaps": sorted(([k, v / 1e9] for k, v in by.items()),
                                key=lambda kv: -kv[1]),
            "named_share": named / work if work else None}


def slowest(tr, spans: dict) -> dict:
    """The longest step of each kind in the window, in ms, with the
    phases inside it."""
    lo, hi = tr.window
    out = {}
    for step in STEPS:
        inside = [(e - s, s, e) for s, e in spans.get(step, ())
                  if lo <= s < hi]
        if not inside:
            continue
        d, s, e = max(inside)
        out[step] = {"ms": d / 1e6, **{
            n: devtrace.overlap_ns(spans[n], s, e) / 1e6
            for n in PHASES if n in spans
            and devtrace.overlap_ns(spans[n], s, e)}}
    return out

