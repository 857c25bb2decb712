"""Scenario-driven policy auto-tuning: per-scenario frontier + winner tables.

Searches the policy space (all 9 kinds x their parameter grids, coarse
grid + successive-halving refinement — ``repro.tuning``) for every
selected catalog scenario under a degradation budget, entirely on the
batched compiled pipeline, and prints each scenario's energy/degradation
Pareto frontier plus the minimum-energy policy that respects the budget.

Usage:
    python experiments/scripts/tune_policies.py [--scale tiny|small|paper]
        [--scenarios a,b,c | --families ml,hpc,dc,app] [--nodes N]
        [--budget PCT] [--rounds N] [--keep K] [--space default|tiny]
        [--objective link_energy|total_energy] [--max-group N] [--csv PATH]

Examples:
    # full catalog, 1% budget, 3 search rounds, 80-node Megafly
    python experiments/scripts/tune_policies.py

    # the datacenter family under a tight 0.2% budget, CSV out
    python experiments/scripts/tune_policies.py --families dc \\
        --budget 0.2 --csv tuned.csv
"""
import argparse
import contextlib
import csv
import os
import sys
import time

sys.path.insert(0, "src")
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from run_suite import get_topo

from repro import scenarios as SC
from repro import tuning
from repro.distributed import shard_sweep
from repro.launch.compile_cache import enable_compile_cache
from repro.scenarios.catalog import FAMILIES
from repro.traffic.plan import PACKINGS, format_cache_info


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", choices=["tiny", "small", "paper"],
                    default="small")
    ap.add_argument("--scenarios", default=None,
                    help="comma-separated catalog names (default: all)")
    ap.add_argument("--families", default=None,
                    help=f"restrict to families, e.g. dc (have: "
                         f"{','.join(FAMILIES)})")
    ap.add_argument("--nodes", type=int, default=None,
                    help="rescale every scenario's allocation "
                         "(default: 8 tiny / catalog size otherwise)")
    ap.add_argument("--budget", type=float, default=1.0, metavar="PCT",
                    help="degradation budget: max exec overhead vs each "
                         "scenario's own baseline, percent")
    ap.add_argument("--rounds", type=int, default=3,
                    help="coarse round + successive-halving refinements")
    ap.add_argument("--keep", type=int, default=4,
                    help="survivors refined per scenario per round")
    ap.add_argument("--space", choices=["default", "tiny"],
                    default="default")
    ap.add_argument("--objective", choices=list(tuning.OBJECTIVES),
                    default="link_energy")
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
            SC.get_scenario(n)           # fail loudly on unknown names
    elif args.families:
        names = []
        for f in args.families.split(","):
            members = SC.list_scenarios(f)
            if not members:
                sys.exit(f"unknown family {f!r}; have {sorted(FAMILIES)}")
            names += members
    n_nodes = args.nodes or (8 if args.scale == "tiny" else None)
    space = tuning.tiny_space() if args.space == "tiny" \
        else tuning.default_space()

    n_scen = len(names) if names is not None else len(SC.list_scenarios())
    n_cand = len(tuning.space_candidates(space)[0])
    print(f"# tuning {n_scen} scenarios x {n_cand} coarse candidates, "
          f"budget <= {args.budget:g}%, {args.rounds} rounds on "
          f"{topo.n_nodes}-node topology", flush=True)
    t0 = time.time()
    with shard_sweep.use_mesh() if args.mesh else contextlib.nullcontext():
        report = tuning.tune_scenarios(
            topo, names, budget_pct=args.budget, rounds=args.rounds,
            space=space, keep=args.keep, n_nodes=n_nodes,
            objective=args.objective, max_group=args.max_group,
            packing=args.packing)
    print(f"# search done in {time.time() - t0:.1f}s; per-round "
          f"(cells, compiles): "
          f"{[(r['cells'], r['compiles']) for r in report.rounds]}",
          flush=True)
    print(f"# {format_cache_info()}", flush=True)
    print(tuning.format_report(report))
    rows = list(tuning.report_rows(report))
    if args.csv and rows:
        with open(args.csv, "w", newline="") as f:
            w = csv.DictWriter(f, fieldnames=list(rows[0]))
            w.writeheader()
            w.writerows(rows)
        print(f"# wrote {len(rows)} rows to {args.csv}")


if __name__ == "__main__":
    main()
