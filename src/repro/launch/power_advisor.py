"""Power advisor: recommend an EEE link power policy for a workload.

The recommendation comes before the workload runs on the fabric, from two
front doors, both on the paper's coupled simulator:

* ``advise_scenario`` runs the auto-tuner (``repro.tuning``) for a NAMED
  catalog workload class under a degradation budget — "my traffic looks
  like dc-onoff and I can afford 1%" comes back as tuned knob settings
  plus the energy/degradation frontier they sit on;
* ``advise_stream`` follows a named DRIFTING arrival stream window by
  window with the closed-loop online advisor (``repro.streaming``),
  switching policies with hysteresis under the budget.

CLI: ``python -m repro.launch.power_advisor --scenario NAME`` or
``--stream DRIFT`` (``--help`` for the knobs).
"""
from __future__ import annotations

from repro.core.eee import PowerModel
from repro.topology.megafly import small_topology


def advise_scenario(scenario: str, *, budget_pct: float = 1.0,
                    topo=None, n_nodes: int | None = None, rounds: int = 3,
                    space=None, objective: str = "link_energy",
                    pm: PowerModel | None = None) -> dict:
    """Recommend a power policy for a named catalog workload class.

    The scenario-name front door to the auto-tuner (``repro.tuning``):
    an operator who knows their workload resembles e.g. ``dc-onoff`` and
    can tolerate ``budget_pct`` percent slowdown gets back the tuned knob
    settings plus the energy/degradation frontier those knobs sit on.
    Defaults to the 80-node small Megafly (CPU-friendly); pass
    ``topo=paper_topology()`` for the §4 system.

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
    """Render an ``advise_stream`` report as the CLI/experiment table.

    ``re-advice ms`` is the window's host time summed over its layers
    (``host_ms``); ``slowest`` names the layer that took most of it."""
    print(f"stream: {out['stream']} ({out['drift']}, "
          f"{out['windows']} windows)  budget <= "
          f"{out['budget_pct']:g}% overhead  objective={out['objective']}")
    print(f"pool: {', '.join(out['pool'])}")
    print(f"  {'w':>3s} {'rate/s':>8s} {'incumbent':28s} {'ovh%':>7s} "
          f"{'saved%':>7s} {'compiles':>8s} {'re-advice ms':>12s} "
          f"{'slowest':9s}  switch")
    for r in out["timeline"]:
        sw = f"-> {r['next_incumbent']} ({r['reason']})" \
            if r["switched"] else ""
        host = r["host_ms"]
        print(f"  {r['window']:3d} {r['rate']:8.0f} {r['incumbent']:28s} "
              f"{r['overhead_pct']:7.3f} {r['saved_pct']:7.2f} "
              f"{r['compiles']:8d} {sum(host.values()):12.1f} "
              f"{max(host, key=host.get):9s}  {sw}")
    t = out["totals"]
    print(f"switches: {out['switches']}   final incumbent: "
          f"{out['final_incumbent']}")
    print(f"online:      saved={t['online_saved_pct']:6.2f}%  "
          f"ovh={t['online_overhead_pct']:.3f}%")
    print(f"best static: saved={t['best_static_saved_pct']:6.2f}%  "
          f"({t['best_static']})")
    print(f"gain vs best-static-in-hindsight: "
          f"{t['gain_vs_static_pct']:.2f}%")


def main():
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--scenario", default=None, metavar="NAME",
                    help="catalog mode: tune for a named workload class "
                         "(repro.scenarios catalog)")
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
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if (args.scenario is None) == (args.stream is None):
        ap.error("pass exactly one of --scenario (catalog workload) or "
                 "--stream (drifting stream)")
    if args.stream:
        topo = small_topology(n_groups=3, leaves=2, spines=2,
                              nodes_per_leaf=2) if args.small_topo else None
        out = advise_stream(
            args.stream,
            budget_pct=0.1 if args.budget is None else args.budget,
            topo=topo, n_nodes=args.n_nodes, windows=args.windows)
        print_stream_report(out)
        return
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


if __name__ == "__main__":
    main()
