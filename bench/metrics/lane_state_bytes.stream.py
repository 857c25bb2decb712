"""Mean bytes per re-advised window of the lanes' initial replay state
that the program made on the device (the counter ``lane_state_bytes``:
each group's net and predictor state, node clocks and latency
accumulators, as ``init_lanes_multi`` creates them).  A program that
keeps no such counter gives nothing to read."""
from window_layers import count, windows


def read(run):
    rows = windows(run)
    if not rows or any("lane_state_bytes" not in row["counts"]
                       for _, row in rows):
        return None
    return count(run, "lane_state_bytes")
