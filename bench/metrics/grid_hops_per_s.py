"""Simulated message-hops answered per wall second: every (trace, lane)
a question replays, the hidden baseline lane included, times that trace's
message-hops on its routes, summed over the questions completed in the
window and divided by the wall seconds those questions took."""
from metric_math import window_rate


def read(run):
    qs = [q for q in run.questions if "hops" in q]
    return window_rate([q["hops"] for q in qs], [q["wall_s"] for q in qs])
