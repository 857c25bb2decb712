"""Surface ``suite``: an operator's (scenario x policy) grid question.

One question is one ``scenarios.run_suite`` call: the traffic file's
scenarios, synthesized on the question's seed at the configuration's
allocation size, replayed under the traffic file's policies plus the
hidden always-on baseline, and reported relative to each scenario's own
baseline.  Before the call the question synthesizes its traces and lowers
their plans itself, under a host span of its own, so the host's share of
the question is timed apart; ``run_suite`` then finds both in the
program's caches.

Its work is the message-hops it answers: each trace's hops on its routes,
times the lanes that replay it (the policies and the baseline).  The
harness has them counted by ``tally`` once the window has closed.

The check: from ``--seed``, draw ``check.questions`` of the questions
the window completed; replay every (scenario, lane) of each on the frozen
host step-loop reference (``refsim``) on the CPU, and compare every
number of every row:

* ``rel_gap``: the widest relative gap of a ``SimResult`` field;
* ``pct_gap``: the widest gap, in percentage points, of the four figures
  relative to the baseline;
* ``missing``: rows the program did not return.
"""
from __future__ import annotations

import time

import surface_util

PCT_FIELDS = ("exec_overhead_pct", "latency_overhead_pct",
              "energy_saved_pct", "link_energy_saved_pct")
BASELINE = "baseline"


def prepare(config: dict, traffic: dict) -> dict:
    from repro.core.eee import Policy
    return dict(surface_util.base_state(config, traffic),
                policies={n: Policy(**kw)
                          for n, kw in traffic["policies"].items()})


def specs(state: dict, seed: int) -> list:
    from repro.scenarios.spec import Scenario, params_of
    n = state["config"]["n_nodes"]
    return [Scenario(s["name"], s["family"], s["builder"], n, seed=seed,
                     params=params_of(**s["params"]))
            for s in state["traffic"]["scenarios"]]


def ref_traces(state: dict, seed: int) -> dict:
    from refsim import traffic as ref_traffic
    n = state["config"]["n_nodes"]
    return {s["name"]: ref_traffic.build(s["builder"], state["ref_topo"], n,
                                         seed, s["params"])
            for s in state["traffic"]["scenarios"]}


def question(state: dict, seed: int) -> dict:
    from harness import span
    from repro.scenarios.spec import build_trace
    from repro.scenarios.suite import run_suite
    from repro.traffic import plan
    t0 = time.perf_counter()
    topo = state["topo"]
    sc = specs(state, seed)
    with span("bench.host_prep"):
        for spec in sc:
            plan.compile_plan(build_trace(spec, topo), topo)
    prep_s = time.perf_counter() - t0
    with span("bench.run_suite"):
        rows = run_suite(topo, sc, state["policies"], state["pm"],
                         baseline=BASELINE)
    return {"prep_s": prep_s, "rows": rows}


def tally(state: dict, q: dict):
    """The question's message-hops, counted after the window on the frozen
    reference's traces and routes, so that none of the yardstick's own
    work is timed with the question."""
    from metric_math import trace_hops
    lanes = len(state["policies"]) + 1
    q["hops"] = lanes * sum(trace_hops(tr, state["ref_topo"])
                            for tr in ref_traces(state, q["seed"]).values())


def reference(state: dict, seed: int) -> dict:
    """``{scenario: {lane: row}}`` from the frozen reference, in the
    precision the process runs it in."""
    from refsim import eee as ref_eee
    from refsim import sim as ref_sim
    pm = ref_eee.PowerModel(**state["config"]["power_model"])
    pols = {n: ref_eee.Policy(**kw)
            for n, kw in state["traffic"]["policies"].items()}
    out = {}
    for name, tr in ref_traces(state, seed).items():
        base, _ = ref_sim.simulate_trace_reference(
            tr, state["ref_topo"], ref_eee.Policy(kind="none"), pm)
        res = {n: ref_sim.simulate_trace_reference(tr, state["ref_topo"],
                                                   p, pm)[0]
               for n, p in pols.items()}
        out[name] = ref_sim.relative_rows(base, res, BASELINE)
    return out


def compare(got: dict, want: dict) -> dict:
    """The three compared numbers of one question's rows."""
    rel = pct = 0.0
    missing = 0
    for sc, rows in want.items():
        for lane, w in rows.items():
            g = got.get(sc, {}).get(lane)
            if g is None:
                missing += 1
                continue
            for k, v in w.items():
                gap = abs(float(g[k]) - float(v))
                if k in PCT_FIELDS:
                    pct = max(pct, gap)
                else:
                    rel = max(rel, gap / max(abs(float(v)), 1e-300))
    return {"rel_gap": rel, "pct_gap": pct, "missing": missing}


def answer(q: dict) -> dict:
    return q["rows"]


def summary(run) -> dict:
    qs = run.questions
    return {"hops_per_question": qs[0]["hops"] if qs else None,
            "question_s": [q["wall_s"] for q in qs],
            "prep_s": [q["prep_s"] for q in qs]}
