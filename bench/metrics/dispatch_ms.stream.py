"""Mean host milliseconds per re-advised window spent enqueueing the replay
programs of its static policy groups (spans ``repro.replay.dispatch``)."""
from window_layers import host_ms


def read(run):
    return host_ms(run, "dispatch")
