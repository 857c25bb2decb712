"""Chip smoke test: the operator's main path, end to end, on one TPU.

    python3 chip_smoke.py                # one chip: suite, advise, stream,
                                         # decoupled kernels
    python3 chip_smoke.py --four-chips   # four chips: the sharded suite grid
                                         # against the single-device grid

Phases (one chip), each through the public entry points:

* ``suite``  — ``scenarios.run_suite`` on the paper's 4160-node Megafly:
  the four ``dc-*`` scenarios and ``app-lammps`` at the paper's 64-node
  allocation, under the 9-policy ``default_policy_grid``.  The cold pass
  runs one static policy group at a time, so every group's compile time
  prints on its own line; the warm pass replays the whole grid at once.
  One ``fixed`` and one ``perfbound`` lane of ``dc-onoff`` must equal
  (``==``) a serial ``simulate_trace`` of the same trace.
* ``advise`` — ``power_advisor.advise_scenario`` for ``dc-onoff`` at a
  0.2% budget on the 80-node Megafly (the tuner's ``tiny_space``, one
  refinement round).
* ``stream`` — ``power_advisor.advise_stream`` on ``drift-dc-diurnal``,
  4 windows, a 3-candidate pool; every window after window 0 runs under
  ``instrument.compile_guard(..., 0)``.
* ``decoupled`` — the three Pallas kernels compiled for the chip (not
  interpreted) at the paper's 20800 port-ends, against ``kernels/ref.py``.

Each phase prints one JSON line: cold and warm wall seconds (to a host
readback), programs through the backend compile step and the seconds spent
there, persistent-cache hits, ``peak_bytes_in_use`` and the device kind.
The last line is the result: ``{"ok": true, "device": {...}}``.  Without a
TPU the script exits non-zero before any phase and prints no result; a
failed phase raises and exits non-zero.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

import jax

ROOT = Path(__file__).resolve().parent
SUITE_SCENARIOS = ("dc-poisson", "dc-hotspot", "dc-onoff", "dc-incast",
                   "app-lammps")
DC_SCENARIOS = SUITE_SCENARIOS[:4]
PAPER_ALLOC = 64                 # the paper's per-application allocation
SERIAL_SCENARIO = "dc-onoff"
SERIAL_LANES = ("fixed-fw-10us", "perfbound-1pct")
KERNEL_E, KERNEL_P, KERNEL_B = 1024, 20800, 200


def require_tpu(count: int):
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {devs[0].platform!r} "
                 f"devices; not running on anything else")
    if len(devs) < count:
        sys.exit(f"chip_smoke: needs {count} TPU chips, found {len(devs)}")
    return devs


def device_info(devs) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak_bytes(dev) -> int | None:
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def timed(fn):
    """Run ``fn`` once; return its result and the pass's wall seconds,
    programs through the backend compile step, their seconds, and
    persistent-cache hits."""
    from repro.core.instrument import count_compiles
    with count_compiles() as cc:
        t0 = time.perf_counter()
        out = fn()
        wall = time.perf_counter() - t0
    return out, {"wall_s": wall, "compiles": cc.count,
                 "compile_s": cc.seconds, "cache_hits": cc.cache_hits}


def report(name: str, dev, cold: dict, warm: dict | None = None, **extra):
    line = {"phase": name, "device_kind": dev.device_kind,
            "cold_s": cold["wall_s"], "cold_compiles": cold["compiles"],
            "cold_compile_s": cold["compile_s"],
            "cold_cache_hits": cold["cache_hits"]}
    if warm is not None:
        line.update(warm_s=warm["wall_s"], warm_compiles=warm["compiles"],
                    warm_cache_hits=warm["cache_hits"])
    line.update(peak_bytes_in_use=peak_bytes(dev), **extra)
    print(json.dumps(line), flush=True)


def check(cond: bool, what: str):
    if not cond:
        raise AssertionError(f"chip_smoke: {what}")


def check_rows(results: dict, what: str):
    for sc, rows in results.items():
        for pol, r in rows.items():
            for k, v in r.items():
                check(math.isfinite(v), f"{what}: {sc}/{pol} {k}={v}")
            check(0.0 <= r["asleep_frac"] <= 1.0,
                  f"{what}: {sc}/{pol} asleep_frac={r['asleep_frac']}")
            check(r["total_energy"] > 0.0, f"{what}: {sc}/{pol} energy")


def phase_suite(dev):
    from repro import scenarios as SC
    from repro.core.eee import PowerModel
    from repro.core.simulator import simulate_trace
    from repro.core.sweep import group_policies
    from repro.scenarios.spec import build_trace
    from repro.scenarios.suite import resolve
    from repro.topology.megafly import paper_topology

    topo = paper_topology()
    grid = SC.default_policy_grid()
    pm = PowerModel()

    def suite(policies):
        return SC.run_suite(topo, list(SUITE_SCENARIOS), policies, pm,
                            n_nodes=PAPER_ALLOC)

    # cold: the always-on baseline first, then one static group per call,
    # so each group's programs compile (or load) on their own line
    cold = {"wall_s": 0.0, "compiles": 0, "compile_s": 0.0, "cache_hits": 0}
    groups = [[]] + group_policies(grid)
    for names in groups:
        _, g = timed(lambda: suite({n: grid[n] for n in names}))
        kind = grid[names[0]].kind if names else "none (baseline)"
        print(json.dumps({"phase": "suite/group", "kind": kind,
                          "policies": names, **g}), flush=True)
        for k in cold:
            cold[k] += g[k]
    res, warm = timed(lambda: suite(grid))
    check_rows(res, "suite")
    check(set(res) == set(SUITE_SCENARIOS), f"suite scenarios {set(res)}")

    spec = resolve([SERIAL_SCENARIO], PAPER_ALLOC)[SERIAL_SCENARIO]
    trace = build_trace(spec, topo)
    for lane in SERIAL_LANES:
        serial, _ = simulate_trace(trace, topo, grid[lane], pm)
        row = res[SERIAL_SCENARIO][lane]
        diff = {k: (v, row[k]) for k, v in serial.as_dict().items()
                if row[k] != v}
        check(not diff, f"suite lane {SERIAL_SCENARIO}/{lane} differs from "
                        f"serial simulate_trace: {diff}")
    report("suite", dev, cold, warm, topology_nodes=topo.n_nodes,
           port_ends=2 * topo.n_links, scenarios=list(SUITE_SCENARIOS),
           policies=len(grid), static_groups=len(groups) - 1,
           serial_equal=[f"{SERIAL_SCENARIO}/{lane}"
                         for lane in SERIAL_LANES])


def phase_advise(dev):
    from repro.launch.power_advisor import advise_scenario
    from repro.tuning import tiny_space

    def advise():
        return advise_scenario("dc-onoff", budget_pct=0.2,
                               space=tiny_space(), rounds=2)

    out, cold = timed(advise)
    again, warm = timed(advise)
    check(out["recommended"] == again["recommended"],
          "advise: warm rerun changed the recommendation")
    check(warm["compiles"] == 0, f"advise: warm rerun compiled "
                                 f"{warm['compiles']} programs")
    check(out["row"]["exec_overhead_pct"] <= 0.2,
          f"advise: winner over budget {out['row']['exec_overhead_pct']}")
    report("advise", dev, cold, warm, recommended=out["recommended"],
           frontier=len(out["frontier"]))


def phase_stream(dev):
    from repro.core.eee import Policy
    from repro.launch.power_advisor import advise_stream

    pool = {
        "fixed-ds-1us": Policy(kind="fixed", t_pdt=1e-6,
                               sleep_state="deep_sleep"),
        "fixed-fw-100us": Policy(kind="fixed", t_pdt=1e-4,
                                 sleep_state="fast_wake"),
        "dual-10us-200us": Policy(kind="dual", t_pdt=1e-5, t_dst=2e-4,
                                  sleep_state="fast_wake",
                                  deep_state="deep_sleep"),
    }

    def stream():
        return advise_stream("drift-dc-diurnal", windows=4, pool=pool)

    out, cold = timed(stream)
    again, warm = timed(stream)
    per_window = [r["compiles"] for r in out["timeline"]]
    check(len(per_window) == 4, f"stream: {len(per_window)} windows")
    check(all(c == 0 for c in per_window[1:]),
          f"stream: windows after 0 compiled {per_window}")
    check(again["totals"] == out["totals"],
          "stream: warm rerun changed the totals")
    report("stream", dev, cold, warm, window_compiles=per_window,
           switches=out["switches"],
           gain_vs_static_pct=out["totals"]["gain_vs_static_pct"])


def phase_decoupled(dev):
    import numpy as np
    from repro.kernels import ops

    rng = np.random.default_rng(0)
    E, P, B = KERNEL_E, KERNEL_P, KERNEL_B
    gaps = rng.uniform(-1e-5, 2e-3, (E, P)).astype(np.float32)
    durs = rng.uniform(0, 1e-4, (E, P)).astype(np.float32)
    durs[rng.random((E, P)) < 0.2] = 0.0
    tpdt = rng.uniform(0, 1e-3, (P,)).astype(np.float32)
    tail = rng.uniform(0, 1.0, (P,)).astype(np.float32)
    hold = rng.uniform(0, 5e-4, (P,)).astype(np.float32)
    counts = rng.integers(0, 20, (P, B)).astype(np.float32)
    centers = ((np.arange(B) + 0.5) * 1e-5).astype(np.float32)
    sums = (counts * centers[None] * rng.uniform(0.9, 1.1, (P, B))
            ).astype(np.float32)
    N = rng.uniform(0, counts.sum(1) + 5).astype(np.float32)
    total = counts.sum(1)
    hist_kw = dict(n_bins=B, bin_width=10e-6)
    energy_kw = dict(t_w=4.48e-6, t_s=2e-6, t_w2=1e-4, t_s2=1e-5)
    select_kw = dict(max_tpdt=10e-3, tpdt_init=1e-3)

    def kernels(use_ref=False):
        return jax.device_get((
            ops.hist_update_op(gaps, **hist_kw, use_ref=use_ref),
            ops.port_energy_op(gaps, durs, tpdt, tail, t_dst=2e-4,
                               hold=hold, **energy_kw, use_ref=use_ref),
            ops.tpdt_select_op(counts, sums, N, total, centers,
                               **select_kw, use_ref=use_ref)))

    got, cold = timed(kernels)
    _, warm = timed(kernels)
    (gc, gs), energy, sel = got
    (wc, ws), want_energy, want_sel = kernels(use_ref=True)
    np.testing.assert_array_equal(gc, wc, err_msg="hist_update counts")
    np.testing.assert_allclose(gs, ws, rtol=1e-5, atol=1e-9,
                               err_msg="hist_update sums")
    for k in energy:
        np.testing.assert_allclose(energy[k], want_energy[k], rtol=1e-5,
                                   atol=1e-8, err_msg=f"port_energy {k}")
    np.testing.assert_allclose(sel, want_sel, rtol=1e-6, atol=0,
                               err_msg="tpdt_select")
    report("decoupled", dev, cold, warm, events=E, port_ends=P, bins=B,
           kernels=["hist_update", "port_energy", "tpdt_select"])


def four_chips(devs):
    """The sharded (trace, lane) grid on four chips against the same grid
    on one device, ``==`` cell for cell."""
    from repro import scenarios as SC
    from repro.core.eee import PowerModel
    from repro.distributed import shard_sweep
    from repro.topology.megafly import paper_topology

    topo = paper_topology()
    grid = SC.default_policy_grid()
    pm = PowerModel()

    def suite():
        return SC.run_suite(topo, list(DC_SCENARIOS), grid, pm,
                            n_nodes=PAPER_ALLOC)

    single, one = timed(suite)
    shard_sweep.placement_cache_clear()
    with shard_sweep.use_mesh():
        sharded, cold = timed(suite)
        _, warm = timed(suite)
    check_rows(sharded, "four-chips")
    diff = [(sc, pol, k) for sc, rows in single.items()
            for pol, r in rows.items() for k, v in r.items()
            if sharded[sc][pol][k] != v]
    check(not diff, f"sharded grid differs from single device: {diff[:8]}")
    mesh = shard_sweep.mesh_for(len(DC_SCENARIOS), 1)
    placed = [shard.device.id for _, pm_, _ in shard_sweep._PLACED.values()
              for shard in pm_.addressable_shards]
    check(len(set(placed)) == len(devs),
          f"trace shards landed on devices {placed}")
    report("four_chips", devs[0], cold, warm, single_device_s=one["wall_s"],
           mesh=dict(mesh.shape), mesh_device_ids=[
               d.id for d in mesh.devices.flat],
           placed_shard_devices=sorted(set(placed)),
           cells=sum(len(r) for r in single.values()), equal=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded suite grid on four chips, "
                         "against the single-device grid")
    args = ap.parse_args()
    devs = require_tpu(4 if args.four_chips else 1)

    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    print(json.dumps({"compile_cache": cache, "device": device_info(devs)}),
          flush=True)
    if args.four_chips:
        four_chips(devs)
    else:
        for phase in (phase_suite, phase_advise, phase_stream,
                      phase_decoupled):
            phase(devs[0])
    print(json.dumps({"ok": True, "device": device_info(devs)}), flush=True)


if __name__ == "__main__":
    main()
