"""Mean bytes per re-advised window that plan lowering placed on the
device (the counter ``placed_bytes``: the new plan's ``plan_nbytes`` on a
plan-cache miss)."""
from window_layers import count


def read(run):
    return count(run, "placed_bytes")
