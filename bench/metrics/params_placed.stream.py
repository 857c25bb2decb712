"""Mean policy groups per re-advised window whose stacked parameters the
program placed on the device (the counter ``params_placed``: each miss
of ``stack_params``' cache; 0 where every group of the pool is warm).  A
program that keeps no such counter gives nothing to read."""
from window_layers import count, windows


def read(run):
    rows = windows(run)
    if not rows or any("params_placed" not in row["counts"]
                       for _, row in rows):
        return None
    return count(run, "params_placed")
