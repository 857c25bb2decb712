"""Shared benchmark scaffolding.

Every bench module exposes ``run(scale) -> list[Row]``.  ``scale``:
  * ``tiny``   — minimal topology (12 nodes) + 2 shortest app traces; the
    CI benchmark-smoke scale (seconds, still exercises the full compiled
    replay pipeline).
  * ``small``  — reduced topology (80 nodes) + shortened app traces; the
    default for ``python -m benchmarks.run`` so the suite finishes on CPU
    in minutes.
  * ``paper``  — the full §4 scenario (4160-node Megafly, 64-node apps).
    Same code path, hours on CPU; the chip's numbers at this scale come
    from ``bench/`` (PERF.md).

Rows print as ``name,us_per_call,derived`` CSV (one per measured quantity).
Modules may additionally expose ``n_policies(scale) -> int`` so the driver
can record grid sizes in the ``BENCH_<name>.json`` perf-trajectory files.
"""
from __future__ import annotations

import dataclasses
import time
from dataclasses import dataclass

import numpy as np

from repro.core.eee import Policy, PowerModel
from repro.topology.megafly import paper_topology, small_topology
from repro.traffic import generators as G


@dataclass
class Row:
    name: str
    us_per_call: float        # wall time of the measured computation
    derived: str              # the quantity the paper's figure/table shows

    def csv(self) -> str:
        return f"{self.name},{self.us_per_call:.1f},{self.derived}"


def timed(fn, *args, **kw):
    t0 = time.perf_counter()
    out = fn(*args, **kw)
    return out, (time.perf_counter() - t0) * 1e6


def get_topo(scale: str):
    if scale == "paper":
        return paper_topology()
    if scale == "tiny":
        return small_topology(n_groups=3, leaves=2, spines=2,
                              nodes_per_leaf=2)
    return small_topology()


def get_apps(scale: str, topo):
    if scale == "paper":
        return {
            "lammps": G.lammps(topo, n_nodes=64, iters=40),
            "patmos": G.patmos(topo, n_nodes=64, compute_secs=1285.0),
            "mlwf": G.mlwf(topo, n_nodes=64, steps=25, layers=8),
            "alexnet": G.alexnet(topo, n_nodes=64, iters=10),
        }
    if scale == "tiny":
        return {
            "lammps": G.lammps(topo, n_nodes=8, iters=2),
            "alexnet": G.alexnet(topo, n_nodes=8, iters=1),
        }
    return {
        "lammps": G.lammps(topo, n_nodes=16, iters=10),
        "patmos": G.patmos(topo, n_nodes=16, compute_secs=30.0),
        "mlwf": G.mlwf(topo, n_nodes=16, steps=5, layers=4),
        "alexnet": G.alexnet(topo, n_nodes=16, iters=3),
    }


# The paper's evaluation grid (§4): 9 fixed t_PDT values 0 .. 1 s,
# 3 PerfBound thresholds, 3 histogram modes, 2 sleep states.
TPDT_GRID = [0.0, 1e-7, 1e-6, 1e-5, 1e-4, 1e-3, 1e-2, 1e-1, 1.0]
BOUNDS = [0.01, 0.02, 0.05]
HIST_MODES = ["keep_all", "self_clear", "circular"]
SLEEP_STATES = ["fast_wake", "deep_sleep"]

PM = PowerModel()
