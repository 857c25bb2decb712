"""The online advisor loop: windowed replay + closed-loop policy switching.

``advise_stream`` consumes a :class:`~repro.streaming.drift.DriftSpec`
window by window.  Each window is synthesized (seeded, cacheable),
incrementally lowered to a :class:`~repro.traffic.plan.TracePlan` and
replayed against the incumbent policy, a tuned challenger pool and a
hidden always-on baseline lane in ONE batched pass per static policy
group — the existing ``stack_plans``/``sweep_cells`` compiled path, with
the wavefront executor pinned so the program key is traffic-independent.
The switching controller (``repro.streaming.controller``) then folds the
window's scores into its hysteresis state and picks the next window's
incumbent under the degradation budget.

Warm-path contract (DESIGN.md §11): every window of a stream shares one
plan shape and the pool is fixed, so all programs compile on window 0 and
every later window's re-advice compiles ZERO programs — hard-asserted via
``instrument.compile_guard`` (``warm_guard=True``, the default) and pinned
in ``benchmarks/baselines/compile_counts.json`` (``"stream": 0``).

The loop is strictly causal: window ``w`` is served by the incumbent
chosen after window ``w-1``; the counterfactual lanes (challengers the
controller did NOT deploy) cost vmap width, not extra programs, and feed
both the switching decision and the first regret-style evaluation in the
repo — online vs the best single static policy in hindsight.
"""
from __future__ import annotations

import contextlib
from typing import Dict, Optional

from repro.core.eee import Policy, PowerModel
from repro.core.instrument import (compile_guard, count_compiles, span,
                                   span_log)
from repro.core.replay import wavefront_mode
from repro.core.simulator import relative_rows, unused_key
from repro.core.sweep import sweep_cells
from repro.scenarios.registry import list_scenarios
from repro.streaming.controller import ControllerState, SwitchConfig, decide
from repro.streaming.drift import DriftSpec, window_rates, window_trace
from repro.tuning import OBJECTIVES

BASELINE = "baseline"

#: a re-advice's host layers: each the summed spans of one window
HOST_LAYERS = {
    "synth": ("repro.stream.synth",),
    "lower": ("repro.plan.lower", "repro.plan.stack"),
    "dispatch": ("repro.replay.dispatch",),
    "wait": ("repro.replay.wait",),
    "readback": ("repro.sweep.readback",),
    "summarize": ("repro.sweep.summarize",),
    "decide": ("repro.stream.decide",),
}
#: a re-advice's counters: bytes placed on the device, bytes read back,
#: bytes of the lanes' initial replay state made on the device, policy
#: groups whose parameters were placed on the device
COUNTERS = ("readback_bytes", "placed_bytes", "lane_state_bytes",
            "params_placed")


def challenger_pool(topo, *, family: str = "dc", n_nodes: int = 16,
                    budget_pct: float = 1.0, pool_size: int = 6,
                    space=None, rounds: int = 2,
                    objective: str = "link_energy",
                    pm: Optional[PowerModel] = None) -> Dict[str, Policy]:
    """Seed the streaming challenger pool from the auto-tuner.

    Runs ``tuning.tune_scenarios`` over the catalog entries of the drift's
    ``family`` (scaled to the stream's allocation size) and collects, per
    scenario, the budget winner first and then its frontier points by
    ascending energy — the policies that won SOME static workload of the
    family are exactly the candidates worth racing when the live traffic
    drifts between those workloads' regimes.  Deduped by candidate name,
    capped at ``pool_size``; insertion order ranks priors (the first entry
    seeds the stream's initial incumbent).
    """
    from repro.tuning import tiny_space, tune_scenarios
    names = list_scenarios(family)
    assert names, f"no catalog scenarios in family {family!r}"
    report = tune_scenarios(topo, names, budget_pct=budget_pct,
                            rounds=rounds,
                            space=space if space is not None
                            else tiny_space(),
                            n_nodes=n_nodes, objective=objective, pm=pm)
    pool: Dict[str, Policy] = {}
    for tuning in report.scenarios.values():
        order = [tuning.winner] + sorted(
            tuning.frontier, key=lambda p: (p.energy, p.name))
        for p in order:
            if p.policy is not None and p.name not in pool:
                pool[p.name] = p.policy
    assert pool, "tuner returned only the always-on baseline — nothing " \
                 "to race; widen the space or loosen the budget"
    return dict(list(pool.items())[:pool_size])


def _window_scores(rows: dict, objective: str) -> Dict[str, tuple]:
    return {name: (row["exec_overhead_pct"], row[objective])
            for name, row in rows.items()}


def advise_stream(spec: DriftSpec, topo, *,
                  pool: Optional[Dict[str, Policy]] = None,
                  budget_pct: float = 1.0, margin_pct: float = 5.0,
                  min_dwell: int = 2, smooth: float = 0.5,
                  objective: str = "link_energy",
                  pm: Optional[PowerModel] = None,
                  pool_size: int = 6, pool_space=None, pool_rounds: int = 2,
                  wavefront: str = "prefix",
                  warm_guard: bool = True,
                  packing: str = "pow2") -> dict:
    """Run the closed-loop streaming advisor over a drifting stream.

    Returns a report dict:

    * ``timeline`` — one row per advisor window: mean arrival ``rate``,
      the ``incumbent`` that served the window, its ``overhead_pct`` /
      ``energy`` / ``saved_pct`` vs the window's own baseline, whether the
      controller ``switched`` afterwards (and why), and the window's
      backend-compile count (0 after window 0 — the warm-path contract);
      ``host_ms`` — the re-advice's host milliseconds by layer
      (``HOST_LAYERS``: ``synth`` drift synthesis, ``lower`` plan
      lowering and stacking, ``dispatch`` enqueueing the replays,
      ``wait`` blocked on the device, ``readback`` net state to host,
      ``summarize`` the per-lane statistics, ``decide`` the controller
      and accounting), each the summed ``instrument.span`` durations of
      that layer; and ``counts`` — ``placed_bytes`` (plan bytes put on
      the device), ``readback_bytes`` (bytes brought back to host) and
      ``lane_state_bytes`` (the lanes' initial replay state, net and
      predictor, made on the device) and ``params_placed`` (policy groups
      whose stacked parameters were placed on the device: 0 once the
      pool is warm);
    * ``totals`` — stream-level accounting: online energy/overhead vs the
      always-on baseline AND vs the best single static policy in
      hindsight (the lowest-total-energy pool candidate whose TOTAL
      overhead respects the budget), plus the regret-style
      ``gain_vs_static_pct``;
    * ``pool`` / ``controller`` / ``switches`` — the racing lanes, the
      hysteresis config, and the switch count.

    ``pool`` defaults to :func:`challenger_pool` seeded from the drift's
    catalog family; the first pool entry is the initial incumbent (the
    tuned prior).  ``wavefront`` pins the message-phase executor for every
    window replay (the adaptive ``auto`` mode may pick different lowerings
    for windows with different live-message densities, which would break
    the zero-compile warm path; all modes are bit-identical).
    ``warm_guard`` hard-asserts the contract: any window after the first
    that compiles a program raises ``CompileGuardError``.
    """
    assert objective in OBJECTIVES, \
        f"objective {objective!r} not in {OBJECTIVES}"
    pm = pm or PowerModel()
    if pool is None:
        pool = challenger_pool(topo, family=spec.family,
                               n_nodes=spec.n_nodes, budget_pct=budget_pct,
                               pool_size=pool_size, space=pool_space,
                               rounds=pool_rounds, objective=objective,
                               pm=pm)
    assert pool, "empty challenger pool"
    base_key = unused_key(pool)
    lanes = {base_key: Policy(kind="none"), **pool}

    cfg = SwitchConfig(budget_pct=budget_pct, margin_pct=margin_pct,
                       min_dwell=min_dwell, smooth=smooth)
    state = ControllerState(incumbent=next(iter(pool)))
    rates = window_rates(spec).mean(axis=1)

    timeline = []
    totals: Dict[str, dict] = {n: {"energy": 0.0, "makespan": 0.0}
                               for n in (BASELINE, *pool)}
    online = {"energy": 0.0, "makespan": 0.0}
    for w in range(spec.windows):
        with span_log() as log, \
                span("repro.stream.window", window=w, stream=spec.name):
            with span("repro.stream.synth"):
                trace = window_trace(spec, topo, w)
            guard = (compile_guard(f"stream window {w} re-advice", 0)
                     if warm_guard and w > 0 else count_compiles())
            with guard as cc, wavefront_mode(wavefront):
                wname = trace.name
                res = sweep_cells({wname: trace}, topo, {wname: lanes}, pm,
                                  packing=packing)[wname]
            with span("repro.stream.decide"):
                base = res.pop(base_key)
                rows = relative_rows(base, res, BASELINE)

                served = state.incumbent     # chosen before seeing window w
                for name in totals:
                    totals[name]["energy"] += rows[name][objective]
                    totals[name]["makespan"] += rows[name]["makespan"]
                online["energy"] += rows[served][objective]
                online["makespan"] += rows[served]["makespan"]

                state, switched, reason = decide(
                    state, _window_scores(rows, objective), cfg)
                row = {
                    "window": w, "rate": float(rates[w]),
                    "incumbent": served,
                    "overhead_pct": rows[served]["exec_overhead_pct"],
                    "energy": rows[served][objective],
                    "saved_pct": 100 * (1 - rows[served][objective]
                                        / rows[BASELINE][objective])
                    if rows[BASELINE][objective] else 0.0,
                    "switched": switched, "reason": reason,
                    "next_incumbent": state.incumbent, "compiles": cc.count,
                }
                timeline.append(row)
            row["host_ms"] = {k: log.ms(*names)
                              for k, names in HOST_LAYERS.items()}
            row["counts"] = {k: log.counts.get(k, 0) for k in COUNTERS}

    base_tot = totals[BASELINE]
    def _ovh(t):
        return (100 * (t["makespan"] / base_tot["makespan"] - 1)
                if base_tot["makespan"] else 0.0)
    def _saved(t):
        return (100 * (1 - t["energy"] / base_tot["energy"])
                if base_tot["energy"] else 0.0)
    static_rows = {n: {"energy": t["energy"], "overhead_pct": _ovh(t),
                       "saved_pct": _saved(t)}
                   for n, t in totals.items() if n != BASELINE}
    feasible = {n: r for n, r in static_rows.items()
                if r["overhead_pct"] <= budget_pct}
    # baseline fallback, as everywhere else: a best-static always exists
    best_static = min(feasible, key=lambda n: (feasible[n]["energy"], n)) \
        if feasible else BASELINE
    static_energy = (static_rows[best_static]["energy"] if feasible
                     else base_tot["energy"])
    return {
        "stream": spec.name, "drift": spec.drift, "windows": spec.windows,
        "objective": objective, "budget_pct": budget_pct,
        "pool": list(pool),
        "controller": {"margin_pct": margin_pct, "min_dwell": min_dwell,
                       "smooth": smooth},
        "switches": state.switches,
        "final_incumbent": state.incumbent,
        "timeline": timeline,
        "static_totals": static_rows,
        "totals": {
            "baseline_energy": base_tot["energy"],
            "online_energy": online["energy"],
            "online_overhead_pct": _ovh(online),
            "online_saved_pct": _saved(online),
            "best_static": best_static,
            "best_static_energy": static_energy,
            "best_static_saved_pct": (100 * (1 - static_energy
                                             / base_tot["energy"])
                                      if base_tot["energy"] else 0.0),
            "gain_vs_static_pct": (100 * (1 - online["energy"]
                                          / static_energy)
                                   if static_energy else 0.0),
        },
    }
