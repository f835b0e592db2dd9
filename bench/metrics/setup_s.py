"""Set-up: process start to the first timed request (host clock)."""


def reduce(rec):
    return rec["setup_s"]
