"""Mean over the window's prefill chunks of the engine's ``engine.chunk``
span less its ``engine.sync`` phase: the host's own time in a chunk step
(program spans, read from the traced run's profile)."""
import spans


def reduce(rec):
    return spans.host_ms(rec, "engine.chunk", "engine.sync")
