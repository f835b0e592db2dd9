"""Share of the traced window in which no op ran on the device."""
import devtrace


def reduce(rec):
    tr = rec.get("trace")
    if tr is None or tr.window is None:
        return None
    lo, hi = tr.window
    return 100.0 * (1.0 - devtrace.busy_ns(tr, lo, hi) / (hi - lo))
