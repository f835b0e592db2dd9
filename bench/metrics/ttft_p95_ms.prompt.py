"""95th percentile over every request sent in the window of the time from
when it was due to its first token (host clock). A request with no token
counts with its wait up to the end of the run.

A per-layer reading, not an end-to-end one: with some 27 long requests in
a 51 s window the 95th percentile is the second largest, and one host
stall of 1.5-2 s inside a step (about one run in three on a TPU v5e
host) adds 20-40% to it, where runs without one agree within 2%."""
import harness


def reduce(rec):
    if "requests" not in rec:
        return None
    vals = [(r["times"][0] if r["times"] else rec["t_last"]) - r["due"]
            for r in rec["requests"]]
    v = harness.percentile(vals, 95)
    return None if v is None else 1e3 * v
