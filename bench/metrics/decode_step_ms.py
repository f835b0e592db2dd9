"""Mean wall of the engine's own decode step events."""


def reduce(rec):
    w = [t1 - t0 for k, _, _, t0, t1 in rec.get("steps", ()) if k == "decode"]
    return 1e3 * sum(w) / len(w) if w else None
