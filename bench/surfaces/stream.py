"""Surface ``stream``: the online advisor re-advising a drifting stream.

One question is one ``repro.streaming.online.advise_stream`` call over a
stream drawn on the question's seed: every advisor window is synthesized,
replayed under the always-on baseline and the pool, and folded into the
switching controller, which picks the next window's incumbent.  The
traffic file spells out the stream, the pool and the controller.

A window's re-advice time is taken at the boundary where the advisor's
loop fetches the next window's trace: the harness wraps the module-level
``window_trace`` lookup of ``repro.streaming.online`` for the call (no
program file is edited), and ``advise_stream``'s return marks the end of
the last window.  Each window is also a host span, ``bench.readvise``.

The check: from ``--seed``, draw ``check.questions`` of the questions
the window completed and run the same stream on the frozen reference
(``refsim.stream``) on the CPU:

* ``decisions``: windows whose served incumbent, next incumbent, switch
  or reason differ, plus a differing switch count or final incumbent;
* ``rel_gap``: the widest relative gap of a window's energy or of the
  stream's baseline and online energy;
* ``pct_gap``: the widest gap, in percentage points, of a window's
  overhead or saving;
* ``missing``: windows the program did not report.
"""
from __future__ import annotations

import time

import surface_util

BASELINE = "baseline"


def prepare(config: dict, traffic: dict) -> dict:
    from repro.core.eee import Policy
    return dict(surface_util.base_state(config, traffic),
                pool={n: Policy(**kw) for n, kw in traffic["pool"].items()})


def _spec_fields(state: dict, seed: int) -> dict:
    d = dict(state["traffic"]["drift"])
    params = d.pop("params")
    return dict(d, n_nodes=state["config"]["n_nodes"], seed=seed,
                params=tuple(sorted(params.items())))


class WindowClock:
    """Stands in for ``online.window_trace``: stamps the host clock at
    every fetch and keeps one ``bench.readvise`` span open per window."""

    def __init__(self, inner):
        self.inner = inner
        self.marks = []
        self.open = None

    def __call__(self, spec, topo, w):
        import jax
        self.marks.append(time.perf_counter())
        self.close()
        self.open = jax.profiler.TraceAnnotation("bench.readvise")
        self.open.__enter__()
        return self.inner(spec, topo, w)

    def close(self):
        if self.open is not None:
            self.open.__exit__(None, None, None)
            self.open = None


def question(state: dict, seed: int) -> dict:
    from repro.streaming import online
    from repro.streaming.drift import DriftSpec
    a = state["traffic"]["advisor"]
    spec = DriftSpec(**_spec_fields(state, seed))
    clock = WindowClock(online.window_trace)
    online.window_trace = clock
    try:
        out = online.advise_stream(
            spec, state["topo"], pool=state["pool"],
            budget_pct=a["budget_pct"], margin_pct=a["margin_pct"],
            min_dwell=a["min_dwell"], smooth=a["smooth"],
            objective=a["objective"], pm=state["pm"],
            wavefront=a["wavefront"])
        end = time.perf_counter()
    finally:
        online.window_trace = clock.inner
        clock.close()
    marks = clock.marks + [end]
    return {"readvise_s": [b - a for a, b in zip(marks, marks[1:])],
            "report": out}


def reference(state: dict, seed: int) -> dict:
    from refsim import eee as ref_eee
    from refsim import stream as ref_stream
    a = state["traffic"]["advisor"]
    pool = {n: ref_eee.Policy(**kw)
            for n, kw in state["traffic"]["pool"].items()}
    return ref_stream.advise_stream(
        ref_stream.DriftSpec(**_spec_fields(state, seed)),
        state["ref_topo"], pool,
        ref_eee.PowerModel(**state["config"]["power_model"]),
        budget_pct=a["budget_pct"], margin_pct=a["margin_pct"],
        min_dwell=a["min_dwell"], smooth=a["smooth"],
        objective=a["objective"], base_policy=ref_eee.Policy(kind="none"))


def answer(q: dict) -> dict:
    """The program's report in the reference's shape."""
    r = q["report"]
    return {"timeline": r["timeline"], "switches": r["switches"],
            "final_incumbent": r["final_incumbent"],
            "baseline_energy": r["totals"]["baseline_energy"],
            "online_energy": r["totals"]["online_energy"]}


def _rel(a, b):
    return abs(float(a) - float(b)) / max(abs(float(b)), 1e-300)


def compare(got: dict, want: dict) -> dict:
    decisions = missing = 0
    rel = pct = 0.0
    tl = {r["window"]: r for r in got["timeline"]}
    for w in want["timeline"]:
        g = tl.get(w["window"])
        if g is None:
            missing += 1
            continue
        decisions += any(g[k] != w[k] for k in
                         ("incumbent", "next_incumbent", "switched",
                          "reason"))
        rel = max(rel, _rel(g["energy"], w["energy"]))
        pct = max(pct, abs(g["overhead_pct"] - w["overhead_pct"]),
                  abs(g["saved_pct"] - w["saved_pct"]))
    decisions += (got["switches"] != want["switches"]) \
        + (got["final_incumbent"] != want["final_incumbent"])
    for k in ("baseline_energy", "online_energy"):
        rel = max(rel, _rel(got[k], want[k]))
    return {"decisions": decisions, "rel_gap": rel, "pct_gap": pct,
            "missing": missing}


def summary(run) -> dict:
    from metric_math import percentile
    ms = [1e3 * s for q in run.questions for s in q["readvise_s"]]
    p95, n, beyond = percentile(ms, 95)
    p50 = percentile(ms, 50)[0]
    return {"readvise_ms_p95": p95, "readvise_ms_p50": p50,
            "readvise_samples": n, "readvise_beyond_p95": beyond,
            "question_s": [q["wall_s"] for q in run.questions]}
