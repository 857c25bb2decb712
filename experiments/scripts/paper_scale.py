"""Paper-scale validation: the exact §4 scenario (4160-node Megafly,
64-node app traces) for the headline policies.  Writes CSV to stdout.

Runs on the batched sweep engine: the four headline policies collapse into
three static-structure groups (both fixed-t_PDT variants share one batched
replay).  ``max_group`` caps the policy-batch width so predictor state
(O(B x 10400 links x 200 bins) f64) stays bounded at paper scale."""
import sys, time
sys.path.insert(0, "src")
from repro.core.eee import Policy, PowerModel
from repro.core.simulator import compare_policies
from repro.topology.megafly import paper_topology
from repro.traffic import generators as G
from repro.launch.compile_cache import enable_compile_cache

enable_compile_cache()

topo = paper_topology()
pm = PowerModel()
pols = {
    "fixed_fw_100us": Policy(kind="fixed", t_pdt=100e-6, sleep_state="fast_wake"),
    "fixed_ds_100us": Policy(kind="fixed", t_pdt=100e-6, sleep_state="deep_sleep"),
    "pb_ds_1pct": Policy(kind="perfbound", bound=0.01, sleep_state="deep_sleep"),
    "pbc_ds_1pct": Policy(kind="perfbound_correct", bound=0.01, sleep_state="deep_sleep"),
}
apps = {
    "patmos": G.patmos(topo, n_nodes=64, compute_secs=1285.0),
    "alexnet": G.alexnet(topo, n_nodes=64, iters=10),
    "lammps": G.lammps(topo, n_nodes=64, iters=40),
    "mlwf": G.mlwf(topo, n_nodes=64, steps=25, layers=8),
}
print("app,policy,exec_oh_pct,lat_oh_pct,saved_pct,link_saved_pct,miss_rate", flush=True)
for app, tr in apps.items():
    t0 = time.time()
    out = compare_policies(tr, topo, pols, pm, max_group=8)
    for name, r in out.items():
        mr = r["misses"] / max(r["hits"] + r["misses"], 1)
        print(f"{app},{name},{r['exec_overhead_pct']:.3f},"
              f"{r['latency_overhead_pct']:.2f},{r['energy_saved_pct']:.2f},"
              f"{r['link_energy_saved_pct']:.2f},{mr:.3f}", flush=True)
    print(f"# {app} done in {time.time()-t0:.0f}s", flush=True)
