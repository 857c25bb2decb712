"""Scenario-suite sweep: the named catalog x a policy grid, batched.

Replays every selected scenario under every policy on the multi-trace
batched path (same-shape plans stack along the trace axis; each static
policy group runs the whole stack in one compiled program per segment
shape) and prints per-scenario energy/degradation tables — the paper's §4
protocol generalized over the scenario catalog.

Usage:
    python experiments/scripts/run_suite.py [--scale tiny|small|paper]
        [--scenarios a,b,c | --families ml,hpc,dc,app] [--nodes N]
        [--policies default|dense] [--max-group N] [--csv PATH]

Examples:
    # full catalog, representative 4-policy grid, 80-node Megafly
    python experiments/scripts/run_suite.py

    # the stochastic family under a dense 28-policy grid, paper topology
    python experiments/scripts/run_suite.py --scale paper --families dc \\
        --policies dense --csv suite.csv
"""
import argparse
import contextlib
import csv
import sys
import time

sys.path.insert(0, "src")

import numpy as np

from repro import scenarios as SC
from repro.core.eee import Policy
from repro.core.sweep import group_policies
from repro.distributed import shard_sweep
from repro.launch.compile_cache import enable_compile_cache
from repro.topology.megafly import paper_topology, small_topology
from repro.traffic.plan import PACKINGS, format_cache_info


def get_topo(scale):
    if scale == "paper":
        return paper_topology()
    if scale == "tiny":
        return small_topology(n_groups=3, leaves=2, spines=2,
                              nodes_per_leaf=2)
    return small_topology()


def dense_grid():
    """Beyond-default: 10-point fixed t_PDT curve x 2 sleep states, a
    4-point bound curve for the three adaptive predictors, a 6-point
    demotion-timer curve for the dual-mode ladder, and a 4-point
    coalescing-window curve — one batched replay per kind."""
    grid = {}
    for st in ("fast_wake", "deep_sleep"):
        for t in np.geomspace(1e-6, 1e-2, 10):
            grid[f"fixed-{st}-{t:.2g}"] = Policy(
                kind="fixed", t_pdt=float(t), sleep_state=st)
    for b in (0.005, 0.01, 0.02, 0.05):
        grid[f"pb-{b:g}"] = Policy(kind="perfbound", bound=b)
        grid[f"pbc-{b:g}"] = Policy(kind="perfbound_correct", bound=b)
        grid[f"pbd-{b:g}"] = Policy(kind="perfbound_dual", bound=b,
                                    sleep_state="fast_wake",
                                    deep_state="deep_sleep")
    for td in np.geomspace(2e-5, 2e-3, 6):
        grid[f"dual-{td:.2g}"] = Policy(
            kind="dual", t_pdt=1e-5, t_dst=float(td),
            sleep_state="fast_wake", deep_state="deep_sleep")
    for md in np.geomspace(1e-5, 1e-3, 4):
        grid[f"coalesce-{md:.2g}"] = Policy(
            kind="coalesce", t_pdt=1e-5, t_dst=2e-4, max_delay=float(md),
            max_frames=16, sleep_state="fast_wake",
            deep_state="deep_sleep")
    return grid


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", choices=["tiny", "small", "paper"],
                    default="small")
    ap.add_argument("--scenarios", default=None,
                    help="comma-separated catalog names (default: all)")
    ap.add_argument("--families", default=None,
                    help="restrict to families, e.g. ml,dc")
    ap.add_argument("--nodes", type=int, default=None,
                    help="rescale every scenario's allocation "
                         "(default: 8 tiny / catalog size otherwise)")
    ap.add_argument("--policies", choices=["default", "dense"],
                    default="default")
    ap.add_argument("--max-group", type=int, default=None,
                    help="cap policy-batch width (device memory)")
    ap.add_argument("--packing", choices=list(PACKINGS), default="pow2",
                    help="stacked-plan segment layout (ragged: size-class "
                         "caps + merged tails, same results)")
    ap.add_argument("--mesh", action="store_true",
                    help="shard the (trace, lane) grid over all visible "
                         "devices (repro.distributed.shard_sweep)")
    ap.add_argument("--csv", default=None, metavar="PATH")
    args = ap.parse_args()
    enable_compile_cache()

    topo = get_topo(args.scale)
    names = None
    if args.scenarios:
        names = args.scenarios.split(",")
        for n in names:
            SC.get_scenario(n)               # fail loudly on unknown names
    elif args.families:
        names = []
        for f in args.families.split(","):
            members = SC.list_scenarios(f)
            if not members:
                known = sorted({s.family for s in SC.catalog().values()})
                sys.exit(f"unknown family {f!r}; have {known}")
            names += members
    n_nodes = args.nodes or (8 if args.scale == "tiny" else None)
    grid = dense_grid() if args.policies == "dense" \
        else SC.default_policy_grid()

    n_scen = len(names) if names is not None else len(SC.list_scenarios())
    print(f"# {n_scen} scenarios x {len(grid)} policies "
          f"({len(group_policies(grid))} static groups) on "
          f"{topo.n_nodes}-node topology", flush=True)
    t0 = time.time()
    with shard_sweep.use_mesh() if args.mesh else contextlib.nullcontext():
        res = SC.run_suite(topo, scenarios=names, policies=grid,
                           n_nodes=n_nodes, max_group=args.max_group,
                           packing=args.packing)
    print(f"# suite done in {time.time() - t0:.1f}s", flush=True)
    print(f"# {format_cache_info()}", flush=True)
    print(SC.format_table(res))
    for sc, rows in res.items():
        best = min((p for p in rows if p != "baseline"),
                   key=lambda p: rows[p]["total_energy"], default=None)
        if best:
            print(f"# {sc}: best={best} "
                  f"saved={rows[best]['energy_saved_pct']:.2f}% "
                  f"overhead={rows[best]['exec_overhead_pct']:.2f}%")
    rows = list(SC.table_rows(res))
    if args.csv and rows:
        with open(args.csv, "w", newline="") as f:
            w = csv.DictWriter(f, fieldnames=list(rows[0]))
            w.writeheader()
            w.writerows(rows)
        print(f"# wrote {len(rows)} rows to {args.csv}")


if __name__ == "__main__":
    main()
