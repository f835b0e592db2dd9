"""Mean over the window's decode steps of the engine's ``engine.decode``
span less its ``engine.sync`` phase: the host's own time in a decode
step (program spans, read from the traced run's profile)."""
import spans


def reduce(rec):
    return spans.host_ms(rec, "engine.decode", "engine.sync")
