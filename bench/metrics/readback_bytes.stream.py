"""Mean bytes per re-advised window that the replays brought to the host
(the counter ``readback_bytes``: the net state and each lane's end time
and latency accumulators)."""
from window_layers import count


def read(run):
    return count(run, "readback_bytes")
