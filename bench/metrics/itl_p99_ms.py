"""99th percentile over every gap between consecutive output tokens of
every request (host clock): the gaps in which the engine ran another
request's prefill as well as the decode step."""
import harness


def reduce(rec):
    if "requests" not in rec:
        return None
    vals = [b - a for r in rec["requests"]
            for a, b in zip(r["times"], r["times"][1:])]
    v = harness.percentile(vals, 99)
    return None if v is None else 1e3 * v
