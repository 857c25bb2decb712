"""Mean host milliseconds per re-advised window spent lowering the window's
plan and stacking it (spans ``repro.plan.lower`` and ``repro.plan.stack``:
routing, bucketing, conflict schedule and device placement)."""
from window_layers import host_ms


def read(run):
    return host_ms(run, "lower")
