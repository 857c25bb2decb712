"""The benchmark's own arithmetic: work per question, rates and tails.

Kept with the benchmark so that the yardstick cannot move with the
program: message-hops are counted on the frozen reference routes
(``refsim.topology``), not on the program's.
"""
from __future__ import annotations

import hashlib
import math

import numpy as np


def derive_seed(seed: int, i: int) -> int:
    """Trace seed of question ``i`` of a run started with ``seed``: a
    63-bit digest, so every question of every run draws its own trace and
    nothing the process cached for an earlier question applies."""
    h = hashlib.blake2b(f"{seed}:{i}".encode(), digest_size=8).digest()
    return int.from_bytes(h, "little") >> 1


def trace_hops(trace, topo) -> int:
    """Message-hops of one trace: each message counts the hops of its
    minimal route on ``topo`` (0 for a message to itself)."""
    total = 0
    for step in trace.steps:
        if step.msgs is not None and len(step.msgs):
            total += int(topo.routes(step.msgs[:, 0], step.msgs[:, 1])[2]
                         .sum())
    return total


def window_rate(work, walls) -> float | None:
    """All the work of the questions completed, over all the wall seconds
    they took (a question that started inside the window counts whole)."""
    if not walls:
        return None
    return float(sum(work)) / float(sum(walls))


def percentile(values, q: float):
    """Nearest-rank ``q``-th percentile of ``values`` with its sample
    count and the number of samples above it: ``(value, n, n_beyond)``.
    ``(None, 0, 0)`` for no samples."""
    xs = np.sort(np.asarray(values, np.float64))
    n = len(xs)
    if n == 0:
        return None, 0, 0
    k = max(math.ceil(q / 100.0 * n), 1) - 1
    return float(xs[k]), n, int((xs > xs[k]).sum())
