"""Mean host milliseconds per re-advised window spent turning each lane's
net state into its statistics (spans ``repro.sweep.summarize``:
``simulator.summarize``)."""
from window_layers import host_ms


def read(run):
    return host_ms(run, "summarize")
