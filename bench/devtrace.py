"""Reduction of a profiler trace (``.xplane.pb``) to the per-layer
metrics' raw material: device op intervals named by kernel and program,
the device's busy union, and the host spans the harness writes.

Only ``jax.profiler.ProfileData`` is used. A device plane is one whose
name starts with ``/device:TPU:`` (the CPU test trace names its own);
its ``XLA Ops`` line holds one event per executed op and its
``XLA Modules`` line one per executed program. Host spans are the
harness's ``TraceAnnotation`` events, on whatever host line holds them.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import re
from pathlib import Path

HOST_SPANS = ("window", "engine.step", "add_request", "arrival_wait",
              "stream")
# ops that contain other ops' events (a layer scan is one while op)
CONTAINERS = ("while", "conditional", "call")


def op_name(text: str) -> str:
    """``%mxfp4_matmul_kernel.62 = bf16[..] custom-call(..)`` ->
    ``mxfp4_matmul_kernel.62``: the HLO instruction's name, which holds a
    Pallas kernel's function name."""
    return text.split(" ", 1)[0].lstrip("%").lower()


def op_kind(name: str) -> str:
    """``mxfp4_matmul_kernel.62`` -> ``mxfp4_matmul_kernel``."""
    return re.sub(r"(\.\d+)+$", "", name)


@dataclasses.dataclass
class Op:
    name: str  # HLO instruction name, lower-cased
    start: int  # ns
    end: int  # ns
    module: str  # name of the program (XLA module) it ran in


@dataclasses.dataclass
class Trace:
    ops: list  # [Op] of the first device, by start
    modules: list  # [(start, end, name)] of the first device
    spans: dict  # host span name -> [(start, end)]
    n_devices: int
    busy_by_device: list  # busy ns of each device in the window

    @property
    def window(self) -> tuple[int, int] | None:
        w = self.spans.get("window")
        return (w[0][0], w[0][1]) if w else None


def find_xplane(log_dir: Path) -> Path:
    hits = sorted(glob.glob(str(log_dir / "plugins/profile/*/*.xplane.pb")))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return Path(hits[-1])


def device_planes(pd, prefix: str):
    return [p for p in pd.planes if p.name.startswith(prefix)]


def load(path: Path, **kw) -> Trace:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(str(path)), **kw)


def reduce_profile(pd, device_prefix: str = "/device:TPU:",
                   op_line: str = "XLA Ops",
                   module_line: str = "XLA Modules") -> Trace:
    """Read the trace once: each device op's instruction name, start and
    end, the program it ran in, and the host spans."""
    spans: dict = {}
    for plane in pd.planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in HOST_SPANS:
                    s = int(ev.start_ns)
                    spans.setdefault(ev.name, []).append(
                        (s, s + int(ev.duration_ns)))
    spans = {k: Spans(v) for k, v in spans.items()}
    win = spans.get("window")
    lo, hi = (win[0] if win else (None, None))
    planes = [p for p in device_planes(pd, device_prefix)
              if any(ln.name == op_line for ln in p.lines)]
    ops, modules, busy = [], [], []
    for di, plane in enumerate(planes):
        lines = {ln.name: ln for ln in plane.lines}
        iv = []
        for ev in lines[op_line].events:
            s = int(ev.start_ns)
            e = s + int(ev.duration_ns)
            iv.append((s, e))
            if di == 0:
                ops.append(Op(op_name(ev.name), s, e, ""))
        busy.append(union_ns(iv, lo, hi))
        if di == 0 and module_line in lines:
            modules = sorted(
                (int(ev.start_ns), int(ev.start_ns) + int(ev.duration_ns),
                 ev.name) for ev in lines[module_line].events)
    ops.sort(key=lambda o: o.start)
    starts = [m[0] for m in modules]
    for op in ops:
        k = bisect.bisect_right(starts, op.start) - 1
        if k >= 0 and modules[k][1] >= op.end:
            op.module = modules[k][2].lower()
    return Trace(ops, modules, spans, len(planes), busy)


def union_ns(intervals, lo=None, hi=None) -> int:
    """Length of the union of [start, end) intervals, clipped to [lo, hi]."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if lo is not None:
            s = max(s, lo)
        if hi is not None:
            e = min(e, hi)
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def busy_ns(tr: Trace, lo=None, hi=None) -> int:
    return union_ns([(o.start, o.end) for o in tr.ops], lo, hi)


def kernel_ns(tr: Trace, kernel: str, module: str | None = None) -> tuple:
    """(summed device ns, event count) of ``kernel``'s op events, only
    those in programs whose name holds ``module`` where given."""
    tot, n = 0, 0
    for o in tr.ops:
        if kernel in o.name and (module is None or module in o.module):
            tot += o.end - o.start
            n += 1
    return tot, n


def idle_gaps(tr: Trace, lo: int, hi: int) -> list:
    """Device idle intervals [(start, end)] within [lo, hi]."""
    gaps, cur = [], lo
    for o in tr.ops:
        if o.end <= cur:
            continue
        if o.start > cur:
            gaps.append((cur, min(o.start, hi)))
        cur = max(cur, o.end)
        if cur >= hi:
            break
    if cur < hi:
        gaps.append((cur, hi))
    return [(s, e) for s, e in gaps if e > s]


def overlap_ns(intervals, lo: int, hi: int) -> int:
    """Overlap of sorted, disjoint intervals with [lo, hi]."""
    ends = [e for _, e in intervals] if not isinstance(intervals, Spans) \
        else intervals.ends
    k = bisect.bisect_right(ends, lo)
    tot = 0
    for s, e in intervals[k:]:
        if s >= hi:
            break
        tot += max(0, min(e, hi) - max(s, lo))
    return tot


class Spans(list):
    """Sorted disjoint host spans with their ends kept for bisection."""

    def __init__(self, items):
        super().__init__(sorted(items))
        self.ends = [e for _, e in self]


def gap_owner(tr: Trace, s: int, e: int) -> str:
    """The host span that covers most of a device idle gap."""
    best, name = 0, "none"
    for span in ("arrival_wait", "add_request", "stream", "engine.step"):
        ov = overlap_ns(tr.spans.get(span, ()), s, e)
        if ov > best:
            best, name = ov, span
    return name


def breakdown(tr: Trace, top: int = 10) -> dict:
    """Top device ops by summed time (instructions of one kind together,
    containers such as a layer scan's while op left out), and the idle
    time by the host span that held the gap, each as [[name, seconds],
    ...]."""
    w = tr.window
    lo, hi = w if w else (tr.ops[0].start, tr.ops[-1].end)
    by_op: dict = {}
    for o in tr.ops:
        key = op_kind(o.name)
        if key in CONTAINERS:
            continue
        by_op[key] = by_op.get(key, 0) + max(0, min(o.end, hi)
                                             - max(o.start, lo))
    ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    by_span: dict = {}
    for s, e in idle_gaps(tr, lo, hi):
        k = gap_owner(tr, s, e)
        by_span[k] = by_span.get(k, 0) + (e - s)
    gaps = sorted(by_span.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v / 1e9] for k, v in ops],
            "idle_gaps": [[k, v / 1e9] for k, v in gaps]}
