"""Power advisor: evaluate EEE link power-management policies for a
compiled LLM training/serving job BEFORE running it on hardware.

This is the framework's first-class integration of the paper's technique
with the LLM substrate (DESIGN.md §2 Layer B): the multi-pod dry-run's
compiled HLO gives the collective schedule (bytes, op mix, per-layer loop
structure); this module maps that schedule onto the paper's 4160-node
Megafly as a phase-structured trace and replays it under any Policy with
the coupled simulator.

Traffic attribution (architecture-true for this framework's sharding):
  * all-gather / reduce-scatter / all-to-all / collective-permute traffic
    comes from the model axis (TP/EP/SP) — emitted per layer inside each
    16-node TP group (which sits inside one Megafly group: TP rides the
    cheap local links, as the paper's own LLM motivation suggests);
  * all-reduce traffic is the data-parallel gradient reduction — emitted
    once per step across TP-rank-aligned nodes in different groups.

Compute time per step = HLO_FLOPs / (devices x peak x MFU), so the trace's
compute:communicate duty cycle matches the compiled job.

Two front doors: ``advise`` evaluates a fixed policy grid for a compiled
dry-run cell (above), and ``advise_scenario`` runs the full auto-tuner
(``repro.tuning``) for a NAMED catalog workload class under a degradation
budget — "my traffic looks like dc-onoff and I can afford 1%" comes back
as tuned knob settings plus the frontier they sit on.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

from repro.core.eee import Policy, PowerModel
from repro.core.simulator import compare_policies
from repro.topology.megafly import Megafly, paper_topology, small_topology
from repro.traffic import collectives as C
from repro.traffic.trace import Trace

DRYRUN_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"

PEAK_FLOPS = 197e12          # TPU v5e bf16 / chip


def load_cell(arch: str, shape: str, mesh: str = "16x16",
              dryrun_dir=DRYRUN_DIR) -> dict:
    pod = "pod2" if mesh.startswith("2x") else "pod1"
    path = Path(dryrun_dir) / f"{arch}__{shape}__{pod}.json"
    rec = json.loads(path.read_text())
    if rec.get("status") != "ok":
        raise ValueError(f"cell {path.name} is {rec.get('status')}: "
                         f"{rec.get('reason', rec.get('error'))}")
    return rec


def _tp_dp_split(census: dict):
    """(tp_bytes, dp_bytes) logical bytes per device per step.

    Prefers the census's replica-group axis classification (contiguous
    groups = model axis = TP/EP/SP; strided = data/pod = DP); falls back
    to op-kind (all-reduce = DP) for censuses recorded without it."""
    axis = census.get("per_axis")
    if axis:
        tp = axis.get("tp", 0.0) + axis.get("local", 0.0)
        dp = axis.get("dp", 0.0)
        return tp, dp
    per_op = census.get("per_op", {})
    dp = per_op.get("all-reduce", 0.0)
    tp = sum(v for k, v in per_op.items() if k != "all-reduce")
    return tp, dp


def llm_trace_from_cell(rec: dict, topo: Megafly, *, n_steps: int = 3,
                        tp_degree: int = 16, mfu: float = 0.4,
                        node_offset: int = 0) -> Trace:
    """Build a Megafly trace replaying ``n_steps`` of the compiled job."""
    n_dev = rec["n_devices"]
    assert node_offset + n_dev <= topo.n_nodes
    nodes = np.arange(node_offset, node_offset + n_dev, dtype=np.int64)
    census = rec["collectives"]
    layers = max(list(census.get("while_trip_counts", {}).values()) or [1])
    tp_bytes, dp_bytes = _tp_dp_split(census)
    flops = rec["cost"].get("flops", 0.0)
    step_secs = flops / (PEAK_FLOPS * mfu) if flops else 1e-3

    # Small cells: a TP group can never outgrow the cell.  Without the
    # clamp an 8-device cell with the default tp_degree=16 builds strided
    # dp_groups where ranks >= n_dev are EMPTY arrays (and TP allreduce
    # rounds over a non-power-of-two remainder), so clamp to the largest
    # power of two that fits and let the existing len>=2 guard skip the
    # DP phase when the cell has no data-parallel replication at all.
    eff_tp = min(tp_degree, n_dev)
    eff_tp = 1 << (eff_tp.bit_length() - 1)      # collectives need 2**k
    tp_groups = [nodes[i:i + eff_tp]
                 for i in range(0, n_dev, eff_tp)]
    dp_groups = [nodes[r::eff_tp] for r in range(eff_tp)]
    per_layer = max(int(tp_bytes / max(layers, 1)), 1)

    t = Trace(nodes=nodes, name=f"llm/{rec['arch']}/{rec['shape']}")
    tp_rounds = _merged_allreduce(tp_groups, per_layer)
    dp_rounds = _merged_allreduce(dp_groups, max(int(dp_bytes), 1))
    for _ in range(n_steps):
        comp = step_secs / max(layers, 1)
        for _l in range(layers):
            t.compute(comp)
            if tp_bytes > 0 and tp_rounds:
                t.rounds(tp_rounds)
        if dp_bytes > 0 and dp_rounds:
            t.rounds(dp_rounds, barrier_last=True)
        else:
            t.barrier()
    return t


def _merged_allreduce(groups, nbytes: int) -> list:
    """Ring-allreduce rounds over ``groups``, merged round-by-round so the
    groups run in parallel.  Degenerate groups — empty, singleton, or a
    non-power-of-two remainder the ring collective cannot express — are
    dropped instead of being handed to ``collectives.allreduce`` (which
    asserts 2**k participants), and ragged round counts are merged with
    ``zip_longest`` so a short remainder group never silently truncates
    the longer groups' rounds."""
    import itertools
    per = [C.allreduce(np.asarray(g), nbytes) for g in groups
           if len(g) >= 2 and (len(g) & (len(g) - 1)) == 0]
    if not per:
        return []
    merged = []
    for ring in itertools.zip_longest(*per):
        live = [r for r in ring if r is not None]
        merged.append(live[0] if len(live) == 1
                      else np.concatenate(live))
    return merged


DEFAULT_POLICIES = {
    "fixed_fw_100us": Policy(kind="fixed", t_pdt=100e-6,
                             sleep_state="fast_wake"),
    "fixed_ds_100us": Policy(kind="fixed", t_pdt=100e-6,
                             sleep_state="deep_sleep"),
    "perfbound_1pct": Policy(kind="perfbound", bound=0.01,
                             sleep_state="deep_sleep"),
    "pbc_1pct": Policy(kind="perfbound_correct", bound=0.01,
                       sleep_state="deep_sleep"),
    "pbc_1pct_fw": Policy(kind="perfbound_correct", bound=0.01,
                          sleep_state="fast_wake"),
}


def advise_scenario(scenario: str, *, budget_pct: float = 1.0,
                    topo=None, n_nodes: int | None = None, rounds: int = 3,
                    space=None, objective: str = "link_energy",
                    pm: PowerModel | None = None) -> dict:
    """Recommend a power policy for a named catalog workload class.

    The scenario-name front door to the auto-tuner (``repro.tuning``):
    an operator who knows their workload resembles e.g. ``dc-onoff`` and
    can tolerate ``budget_pct`` percent slowdown gets back the tuned knob
    settings plus the energy/degradation frontier those knobs sit on —
    without a dry-run artifact.  Defaults to the 80-node small Megafly
    (CPU-friendly); pass ``topo=paper_topology()`` for the §4 system.

    Returns ``{'scenario', 'budget_pct', 'recommended', 'policy',
    'frontier', 'rounds'}`` where ``policy`` is the winning
    :class:`~repro.core.eee.Policy` (None when only the always-on
    baseline fits the budget) and ``frontier`` rows carry the §4
    percentages per non-dominated point.
    """
    from repro.scenarios import get_scenario
    from repro.tuning import tune_scenarios
    get_scenario(scenario)               # fail loudly on unknown names
    topo = topo if topo is not None else small_topology()
    report = tune_scenarios(topo, [scenario], budget_pct=budget_pct,
                            rounds=rounds, space=space, n_nodes=n_nodes,
                            objective=objective, pm=pm)
    tuning = report.scenarios[scenario]
    w = tuning.winner
    return {
        "scenario": scenario,
        "budget_pct": budget_pct,
        "recommended": w.name,
        "policy": w.policy,
        "row": w.row,
        "frontier": [{"policy": p.name, "degradation_pct": p.degradation,
                      **{k: p.row[k] for k in ("energy_saved_pct",
                                               "link_energy_saved_pct")}}
                     for p in tuning.frontier],
        "rounds": report.rounds,
    }


def advise_stream(drift: str, *, budget_pct: float = 0.1,
                  topo=None, n_nodes: int | None = None,
                  windows: int | None = None, seed: int | None = None,
                  pool=None, pool_size: int = 6, pool_rounds: int = 2,
                  margin_pct: float = 5.0, min_dwell: int = 2,
                  objective: str = "link_energy",
                  pm: PowerModel | None = None, **kw) -> dict:
    """Run the closed-loop streaming advisor on a named drift stream.

    The live-traffic front door (DESIGN.md §11): where ``advise_scenario``
    answers "my traffic looks like dc-onoff" ONCE, this follows a DRIFTING
    arrival process (``repro.streaming.drift`` catalog: diurnal sine,
    flash crowds, regime switching) window by window, racing the incumbent
    against a tuned challenger pool and switching with hysteresis under
    the degradation budget.  Returns the ``repro.streaming.advise_stream``
    report — per-window timeline plus online-vs-best-static totals.
    """
    from repro.streaming import advise_stream as _advise_stream
    from repro.streaming import get_drift
    spec = get_drift(drift).scaled(n_nodes=n_nodes, windows=windows,
                                   seed=seed)
    topo = topo if topo is not None else small_topology()
    return _advise_stream(spec, topo, budget_pct=budget_pct, pool=pool,
                          pool_size=pool_size, pool_rounds=pool_rounds,
                          margin_pct=margin_pct, min_dwell=min_dwell,
                          objective=objective, pm=pm, **kw)


def print_stream_report(out: dict) -> None:
    """Render an ``advise_stream`` report as the CLI/experiment table."""
    print(f"stream: {out['stream']} ({out['drift']}, "
          f"{out['windows']} windows)  budget <= "
          f"{out['budget_pct']:g}% overhead  objective={out['objective']}")
    print(f"pool: {', '.join(out['pool'])}")
    print(f"  {'w':>3s} {'rate/s':>8s} {'incumbent':28s} {'ovh%':>7s} "
          f"{'saved%':>7s} {'compiles':>8s}  switch")
    for r in out["timeline"]:
        sw = f"-> {r['next_incumbent']} ({r['reason']})" \
            if r["switched"] else ""
        print(f"  {r['window']:3d} {r['rate']:8.0f} {r['incumbent']:28s} "
              f"{r['overhead_pct']:7.3f} {r['saved_pct']:7.2f} "
              f"{r['compiles']:8d}  {sw}")
    t = out["totals"]
    print(f"switches: {out['switches']}   final incumbent: "
          f"{out['final_incumbent']}")
    print(f"online:      saved={t['online_saved_pct']:6.2f}%  "
          f"ovh={t['online_overhead_pct']:.3f}%")
    print(f"best static: saved={t['best_static_saved_pct']:6.2f}%  "
          f"({t['best_static']})")
    print(f"gain vs best-static-in-hindsight: "
          f"{t['gain_vs_static_pct']:.2f}%")


def advise(arch: str, shape: str, mesh: str = "16x16", *,
           policies: dict | None = None, n_steps: int = 3,
           mfu: float = 0.4, max_overhead_pct: float = 1.0,
           topo: Megafly | None = None, pm: PowerModel | None = None,
           dryrun_dir=DRYRUN_DIR) -> dict:
    """Evaluate policies for a dry-run cell.  Returns
    {'cell', 'table', 'recommended'} — recommended = most total energy
    saved subject to exec overhead <= max_overhead_pct; when no policy
    fits the budget the recommendation falls back to the always-on
    ``"baseline"`` row (0% overhead, 0% saved), mirroring
    ``frontier.budget_winner`` — the advisor never answers None."""
    rec = load_cell(arch, shape, mesh, dryrun_dir)
    topo = topo or paper_topology()
    trace = llm_trace_from_cell(rec, topo, n_steps=n_steps, mfu=mfu)
    table = compare_policies(trace, topo, policies or DEFAULT_POLICIES, pm)
    best, best_saved = "baseline", 0.0
    for name, row in table.items():
        if name == "baseline":
            continue
        if row["exec_overhead_pct"] <= max_overhead_pct \
                and row["energy_saved_pct"] > best_saved:
            best, best_saved = name, row["energy_saved_pct"]
    return {
        "cell": {k: rec[k] for k in ("arch", "shape", "mesh", "n_devices")},
        "tp_dp_bytes": _tp_dp_split(rec["collectives"]),
        "table": table,
        "recommended": best,
    }


def main():
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--arch", default=None,
                    help="dry-run cell mode: compiled-job architecture")
    ap.add_argument("--scenario", default=None, metavar="NAME",
                    help="catalog mode: tune for a named workload class "
                         "(repro.scenarios catalog) instead of a dry-run "
                         "cell")
    ap.add_argument("--stream", default=None, metavar="DRIFT",
                    help="streaming mode: follow a named drifting stream "
                         "(repro.streaming drift catalog) with the "
                         "closed-loop online advisor")
    ap.add_argument("--budget", type=float, default=None, metavar="PCT",
                    help="scenario/stream mode: max exec overhead in "
                         "percent (default 1.0 scenario, 0.1 stream)")
    ap.add_argument("--rounds", type=int, default=3,
                    help="scenario mode: tuner search rounds")
    ap.add_argument("--windows", type=int, default=None,
                    help="stream mode: override the drift's window count")
    ap.add_argument("--n-nodes", type=int, default=None,
                    help="scenario/stream mode: allocation size")
    ap.add_argument("--small-topo", action="store_true",
                    help="stream mode: tiny 12-node Megafly (CI smoke)")
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--mesh", default="16x16")
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--max-overhead-pct", type=float, default=1.0)
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    modes = [m for m in (args.arch, args.scenario, args.stream)
             if m is not None]
    if len(modes) != 1:
        ap.error("pass exactly one of --arch (dry-run cell), --scenario "
                 "(catalog workload) or --stream (drifting stream)")
    if args.stream:
        topo = small_topology(n_groups=3, leaves=2, spines=2,
                              nodes_per_leaf=2) if args.small_topo else None
        out = advise_stream(
            args.stream,
            budget_pct=0.1 if args.budget is None else args.budget,
            topo=topo, n_nodes=args.n_nodes, windows=args.windows)
        print_stream_report(out)
        return
    if args.scenario:
        out = advise_scenario(args.scenario,
                              budget_pct=1.0 if args.budget is None
                              else args.budget,
                              rounds=args.rounds, n_nodes=args.n_nodes)
        print(f"scenario: {out['scenario']}  "
              f"budget <= {out['budget_pct']:g}% overhead")
        for p in out["frontier"]:
            print(f"  {p['policy']:34s} "
                  f"ovh={p['degradation_pct']:7.3f}% "
                  f"saved={p['energy_saved_pct']:6.2f}% "
                  f"link_saved={p['link_energy_saved_pct']:6.2f}%")
        print(f"recommended: {out['recommended']}")
        if out["policy"] is not None:
            print(f"  policy: {out['policy']}")
        return
    out = advise(args.arch, args.shape, args.mesh, n_steps=args.steps,
                 max_overhead_pct=args.max_overhead_pct)
    print(f"cell: {out['cell']}")
    tp, dp = out["tp_dp_bytes"]
    print(f"wire bytes/device/step: TP={tp/2**20:.1f} MiB "
          f"DP={dp/2**20:.1f} MiB")
    for name, row in out["table"].items():
        print(f"  {name:18s} exec_oh={row['exec_overhead_pct']:7.3f}% "
              f"saved={row['energy_saved_pct']:6.2f}% "
              f"link_saved={row['link_energy_saved_pct']:6.2f}%")
    print(f"recommended: {out['recommended']}")


if __name__ == "__main__":
    main()
