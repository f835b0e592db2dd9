"""Frames whose labels returned, over the time from the window's start to
the return of the last chunk started inside it (host clock)."""


def reduce(rec):
    chunks = rec.get("chunks")
    if not chunks:
        return None
    return sum(n for _, n in chunks) / (chunks[-1][0] - rec["t0"])
