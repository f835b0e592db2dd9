"""95th percentile of due time to admission, read from the engine's own
``request_admitted`` span (``serving/scheduler.py`` admission)."""
import harness


def reduce(rec):
    vals = [r["admitted"] - r["due"] for r in rec.get("requests", ())
            if r.get("admitted") is not None]
    v = harness.percentile(vals, 95)
    return None if v is None else 1e3 * v
