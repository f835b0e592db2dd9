"""Tokens emitted inside the window over the window's length (host
clock); tokens of the drain after it do not count."""


def reduce(rec):
    if "requests" not in rec:
        return None
    n = sum(1 for r in rec["requests"] for t in r["times"] if t <= rec["end"])
    return n / rec["seconds"]
