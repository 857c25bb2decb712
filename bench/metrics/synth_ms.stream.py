"""Mean host milliseconds per re-advised window spent synthesizing the
window's trace (span ``repro.stream.synth``:
``streaming.drift.window_trace``)."""
from window_layers import host_ms


def read(run):
    return host_ms(run, "synth")
