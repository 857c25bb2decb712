"""Mean host milliseconds per re-advised window spent bringing the replayed
net state to the host (spans ``repro.sweep.readback``)."""
from window_layers import host_ms


def read(run):
    return host_ms(run, "readback")
