"""Dense beyond-paper policy grid on the batched sweep engine.

The paper's §4 evaluation samples 9 fixed t_PDT points and 3 PerfBound
bounds; per-policy serial replay made anything denser impractical.  The
batched engine removes that constraint: this script sweeps

  * a 25-point log-spaced fixed t_PDT curve x 2 sleep states (ONE batched
    replay per app — all 50 cells share static structure), and
  * a 12-point bound curve for PerfBound and PerfBoundCorrect x 2 sleep
    states (one batched replay per kind),

and prints per-cell CSV plus the per-app energy-optimal cell.  Usage:

    python experiments/scripts/sweep_grid.py [small|paper] [n_nodes]
"""
import sys, time
sys.path.insert(0, "src")
import numpy as np

from repro.core.eee import Policy, PowerModel
from repro.core.sweep import group_policies, sweep_policies
from repro.topology.megafly import paper_topology, small_topology
from repro.traffic import generators as G
from repro.traffic.plan import compile_plan
from repro.launch.compile_cache import enable_compile_cache

enable_compile_cache()

scale = sys.argv[1] if len(sys.argv) > 1 else "small"
if scale not in ("small", "paper"):
    sys.exit(f"usage: sweep_grid.py [small|paper] [n_nodes] "
             f"(got scale={scale!r})")
n_nodes = int(sys.argv[2]) if len(sys.argv) > 2 else (64 if scale == "paper"
                                                      else 16)
topo = paper_topology() if scale == "paper" else small_topology()
pm = PowerModel()
apps = {
    "lammps": G.lammps(topo, n_nodes=n_nodes,
                       iters=40 if scale == "paper" else 10),
    "alexnet": G.alexnet(topo, n_nodes=n_nodes,
                         iters=10 if scale == "paper" else 3),
}

grid = {}
for st in ("fast_wake", "deep_sleep"):
    for t in np.geomspace(1e-7, 1.0, 25):
        grid[f"fixed,{st},{t:.3g}"] = Policy(kind="fixed", t_pdt=float(t),
                                             sleep_state=st)
    for b in np.geomspace(0.002, 0.2, 12):
        for kind, tag in (("perfbound", "pb"), ("perfbound_correct", "pbc")):
            grid[f"{tag},{st},{b:.3g}"] = Policy(kind=kind, bound=float(b),
                                                 sleep_state=st)
# dual-mode FSM curves (DESIGN.md §6): demotion-timer sweep, coalescing
# window sweep, and the adaptive-demotion bound curve — the Fast Wake ->
# Deep Sleep ladder the single-state grid above cannot express
for td in np.geomspace(1e-5, 1e-2, 8):
    grid[f"dual,fw>ds,{td:.3g}"] = Policy(
        kind="dual", t_pdt=1e-5, t_dst=float(td), sleep_state="fast_wake",
        deep_state="deep_sleep")
for md in np.geomspace(1e-5, 1e-3, 6):
    grid[f"coalesce,fw>ds,{md:.3g}"] = Policy(
        kind="coalesce", t_pdt=1e-5, t_dst=2e-4, max_delay=float(md),
        max_frames=16, sleep_state="fast_wake", deep_state="deep_sleep")
for b in np.geomspace(0.002, 0.2, 8):
    grid[f"pbd,fw>ds,{b:.3g}"] = Policy(
        kind="perfbound_dual", bound=float(b), sleep_state="fast_wake",
        deep_state="deep_sleep")

print(f"# {len(grid)} grid cells in {len(group_policies(grid))} batched "
      f"groups", flush=True)
print("app,policy,makespan_s,mean_latency_s,link_energy_J,total_energy_J,"
      "asleep_frac,miss_rate", flush=True)
max_group = 8 if scale == "paper" else None
for app, tr in apps.items():
    # compile the trace plan once up front — EVERY policy group below
    # reuses it from the cache (routes + padding computed once per app)
    t0 = time.time()
    plan = compile_plan(tr, topo)
    print(f"# {plan.describe()} compiled in {time.time() - t0:.1f}s",
          flush=True)
    t0 = time.time()
    out = sweep_policies(tr, topo, grid, pm, max_group=max_group)
    for name, r in out.items():
        mr = r.misses / max(r.hits + r.misses, 1)
        print(f"{app},{name},{r.makespan:.6g},{r.mean_latency:.6g},"
              f"{r.link_energy:.6g},{r.total_energy:.6g},"
              f"{r.asleep_frac:.4f},{mr:.4f}", flush=True)
    best = min(out, key=lambda k: out[k].total_energy)
    print(f"# {app}: best={best} total_e={out[best].total_energy:.6g}J "
          f"({time.time() - t0:.0f}s for {len(grid)} cells)", flush=True)
