"""Streaming power advisor timeline: closed-loop policy switching on a
drifting dc-* stream (DESIGN.md §11).

Runs the online advisor on the named drift-catalog streams and prints a
per-window markdown timeline — arrival rate, the incumbent that served
the window, its overhead/savings vs the window's own always-on baseline,
switches, compile counts — plus the stream-level regret summary: energy
saved online vs the best single static policy in hindsight.

Usage:
  PYTHONPATH=src python experiments/scripts/advise_stream.py \
      [--drift drift-dc-regimes] [--budget 0.1] [--windows 10] \
      [--n-nodes 8] [--tiny] [--json OUT.json]
"""
import argparse
import json
import sys

sys.path.insert(0, "src")

from repro.core.eee import Policy                          # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.power_advisor import advise_stream       # noqa: E402
from repro.topology.megafly import small_topology          # noqa: E402

# Same fixed racing pool as benchmarks/bench_stream.py: the aggressive /
# mild / two-stage regimes the drift catalog flips between.  Drop the
# --pool-tuned flag in to seed from tune_scenarios winners instead.
POOL = {
    "fixed-ds-1us": Policy(kind="fixed", t_pdt=1e-6,
                           sleep_state="deep_sleep"),
    "fixed-fw-100us": Policy(kind="fixed", t_pdt=1e-4,
                             sleep_state="fast_wake"),
    "dual-10us-200us": Policy(kind="dual", t_pdt=1e-5, t_dst=2e-4,
                              sleep_state="fast_wake",
                              deep_state="deep_sleep"),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--drift", default="drift-dc-regimes")
    ap.add_argument("--budget", type=float, default=0.1)
    ap.add_argument("--windows", type=int, default=None)
    ap.add_argument("--n-nodes", type=int, default=None)
    ap.add_argument("--tiny", action="store_true",
                    help="12-node Megafly + 8-node stream (CI smoke)")
    ap.add_argument("--pool-tuned", action="store_true",
                    help="seed the pool from tune_scenarios winners "
                         "instead of the fixed racing pool")
    ap.add_argument("--json", default=None, metavar="OUT")
    args = ap.parse_args()
    enable_compile_cache()

    topo = small_topology(n_groups=3, leaves=2, spines=2,
                          nodes_per_leaf=2) if args.tiny else None
    out = advise_stream(
        args.drift, budget_pct=args.budget, topo=topo,
        n_nodes=8 if args.tiny and args.n_nodes is None else args.n_nodes,
        windows=args.windows,
        pool=None if args.pool_tuned else POOL)

    print(f"### {out['stream']} ({out['drift']}, {out['windows']} windows, "
          f"budget <= {out['budget_pct']:g}% overhead)\n")
    print("| w | rate/s | incumbent | ovh% | saved% | compiles | switch |")
    print("|---|---|---|---|---|---|---|")
    for r in out["timeline"]:
        sw = (f"→ {r['next_incumbent']} ({r['reason']})"
              if r["switched"] else "")
        print(f"| {r['window']} | {r['rate']:.0f} | {r['incumbent']} | "
              f"{r['overhead_pct']:.3f} | {r['saved_pct']:.2f} | "
              f"{r['compiles']} | {sw} |")
    t = out["totals"]
    print(f"\nswitches: {out['switches']}")
    print(f"online:      link energy saved {t['online_saved_pct']:.2f}% "
          f"(overhead {t['online_overhead_pct']:.3f}%)")
    print(f"best static: link energy saved {t['best_static_saved_pct']:.2f}%"
          f" ({t['best_static']})")
    print(f"gain vs best-static-in-hindsight: "
          f"{t['gain_vs_static_pct']:.2f}%")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=1, sort_keys=True, default=str)
        print(f"\nwrote {args.json}")


if __name__ == "__main__":
    main()
