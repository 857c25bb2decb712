"""Mean host milliseconds per re-advised window spent blocked on the
device's replays, at the first read of their results (spans
``repro.replay.wait``)."""
from window_layers import host_ms


def read(run):
    return host_ms(run, "wait")
