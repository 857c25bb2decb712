"""Mean host milliseconds per re-advised window spent in selection: the
rows against the baseline, the switching controller and the stream's
accounting (span ``repro.stream.decide``)."""
from window_layers import host_ms


def read(run):
    return host_ms(run, "decide")
