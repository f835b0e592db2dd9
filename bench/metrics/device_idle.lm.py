"""Share of the traced window in which no op ran on the device, over the
time the engine had work: the harness's ``arrival_wait`` spans (nothing
due, nothing queued) are left out of both."""
import devtrace


def reduce(rec):
    tr = rec.get("trace")
    if tr is None or tr.window is None:
        return None
    lo, hi = tr.window
    waits = tr.spans.get("arrival_wait", [])
    span = (hi - lo) - devtrace.overlap_ns(waits, lo, hi)
    if span <= 0:
        return None
    idle = sum(e - s - devtrace.overlap_ns(waits, s, e)
               for s, e in devtrace.idle_gaps(tr, lo, hi))
    return 100.0 * idle / span
