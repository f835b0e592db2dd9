"""Mean wall of the engine's own prefill step events (single-shot or one
chunk), each ending in the step's device sync."""


def reduce(rec):
    w = [t1 - t0 for k, _, _, t0, t1 in rec.get("steps", ()) if k == "prefill"]
    return 1e3 * sum(w) / len(w) if w else None
