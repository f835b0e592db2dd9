"""Mean over the window's frames of the vision engine's ``vision.frame``
span less its ``vision.sync`` phase: the host's own time in a frame
(program spans, read from the traced run's profile)."""
import spans


def reduce(rec):
    return spans.host_ms(rec, "vision.frame", "vision.sync")
