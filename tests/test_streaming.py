"""Streaming power advisor (DESIGN.md §11): drift synthesis invariants,
hysteresis-controller properties, window-replay equivalence to the serial
simulator, the warm-path zero-compile contract, and the regret acceptance
gate (online strictly beats the best static policy in hindsight on a
drifting dc-* stream, within the degradation budget)."""
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import simulator as S
from repro.core.eee import Policy, PowerModel
from repro.core.replay import wavefront_mode
from repro.core.sweep import sweep_cells
from repro.streaming import (ControllerState, DriftSpec, SwitchConfig,
                             advise_stream, decide, get_drift, list_drifts,
                             regime_path, window_rates, window_trace)
from repro.topology.megafly import small_topology

PM = PowerModel()

# The aggressive / mild / two-stage regimes the drift catalog flips
# between (same racing pool as benchmarks/bench_stream.py).
POOL = {
    "fixed-ds-1us": Policy(kind="fixed", t_pdt=1e-6,
                           sleep_state="deep_sleep"),
    "fixed-fw-100us": Policy(kind="fixed", t_pdt=1e-4,
                             sleep_state="fast_wake"),
    "dual-10us-200us": Policy(kind="dual", t_pdt=1e-5, t_dst=2e-4,
                              sleep_state="fast_wake",
                              deep_state="deep_sleep"),
}


@pytest.fixture(scope="module")
def tiny():
    return small_topology(n_groups=3, leaves=2, spines=2, nodes_per_leaf=2)


# ---------------------------------------------------------------------------
# Drift synthesis
# ---------------------------------------------------------------------------


def test_drift_catalog_registered():
    names = list_drifts()
    assert {"drift-dc-diurnal", "drift-dc-flash",
            "drift-dc-regimes"} <= set(names)
    with pytest.raises(KeyError, match="unknown drift"):
        get_drift("no-such-stream")
    for n in names:
        spec = get_drift(n)
        rates = window_rates(spec)
        assert rates.shape == (spec.windows, spec.steps)
        assert (rates > 0).all()
        assert regime_path(spec).shape == (spec.windows,)


def test_drift_spec_validates():
    with pytest.raises(ValueError, match="drift kind"):
        DriftSpec("x", "sawtooth")
    with pytest.raises(ValueError, match="max_flows"):
        DriftSpec("x", "diurnal", max_flows=100)
    with pytest.raises(ValueError, match="degenerate"):
        DriftSpec("x", "diurnal", windows=0)


def test_window_trace_cached_and_seeded(tiny):
    spec = get_drift("drift-dc-regimes").scaled(n_nodes=8, windows=4)
    t0 = window_trace(spec, tiny, 0)
    assert window_trace(spec, tiny, 0) is t0       # identity-stable cache
    t1 = window_trace(spec, tiny, 1)
    assert t0.name != t1.name
    # reseeding changes the draw, same seed re-synthesizes identically
    other = window_trace(spec.scaled(seed=99), tiny, 0)
    assert other.total_bytes != t0.total_bytes
    with pytest.raises(IndexError):
        window_trace(spec, tiny, 4)


def test_windows_share_one_plan_shape(tiny):
    """The tentpole invariant: every window of a stream (quiet or busy)
    lowers to the SAME compiled plan shape, so the whole stream rides one
    program per static policy group."""
    from repro.traffic.plan import compile_plan, plan_shape_key
    spec = get_drift("drift-dc-regimes").scaled(n_nodes=8, windows=6)
    keys = {plan_shape_key(compile_plan(window_trace(spec, tiny, w), tiny))
            for w in range(spec.windows)}
    assert len(keys) == 1
    # flow counts honor the one-bucket clip [2, max_flows]
    for w in range(spec.windows):
        for step in window_trace(spec, tiny, w).steps:
            if step.msgs is not None and len(step.msgs):
                assert 2 <= len(step.msgs) <= spec.max_flows


# ---------------------------------------------------------------------------
# Hysteresis controller (pure logic — property tests)
# ---------------------------------------------------------------------------


def _run_controller(tables, cfg, start):
    """Feed per-window score tables through ``decide``; return the switch
    windows."""
    state = ControllerState(incumbent=start)
    switched_at = []
    for w, scores in enumerate(tables):
        state, switched, _ = decide(state, scores, cfg)
        if switched:
            switched_at.append(w)
    return state, switched_at


@settings(max_examples=25, deadline=None)
@given(data=st.data())
def test_controller_stationary_never_flaps(data):
    """Constant scores => at most ONE switch ever (onto the stationary
    winner), regardless of config."""
    names = ["a", "b", "c"]
    scores = {n: (data.draw(st.floats(0.0, 2.0)),
                  data.draw(st.floats(1.0, 100.0))) for n in names}
    cfg = SwitchConfig(budget_pct=data.draw(st.floats(0.0, 3.0)),
                       margin_pct=data.draw(st.floats(0.0, 20.0)),
                       min_dwell=data.draw(st.integers(1, 4)),
                       smooth=data.draw(st.floats(0.1, 1.0)))
    start = data.draw(st.sampled_from(names))
    state, switched_at = _run_controller([dict(scores)] * 12, cfg, start)
    assert state.switches <= 1
    # and a switch never lands on an over-budget candidate
    if state.switches:
        assert state.ewma[state.incumbent][0] <= cfg.budget_pct


@settings(max_examples=25, deadline=None)
@given(data=st.data())
def test_controller_switches_bounded_by_regime_changes(data):
    """Piecewise-stationary scores: switch count <= regime changes + 1
    (the +1 is the initial correction away from a bad prior), and
    consecutive switches are >= min_dwell windows apart."""
    table_a = {"a": (0.0, 10.0), "b": (0.0, 50.0)}
    table_b = {"a": (0.0, 50.0), "b": (0.0, 10.0)}
    flips = data.draw(st.lists(st.booleans(), min_size=6, max_size=24))
    cfg = SwitchConfig(budget_pct=1.0, margin_pct=5.0,
                       min_dwell=data.draw(st.integers(1, 3)),
                       smooth=data.draw(st.floats(0.3, 1.0)))
    tables = [table_b if f else table_a for f in flips]
    changes = int(np.sum(np.asarray(flips[1:]) != np.asarray(flips[:-1])))
    state, switched_at = _run_controller(
        tables, cfg, data.draw(st.sampled_from(["a", "b"])))
    assert state.switches <= changes + 1
    for i, j in zip(switched_at, switched_at[1:]):
        assert j - i >= cfg.min_dwell


def test_controller_budget_overrides_margin():
    """An incumbent drifting out of budget is evicted even when no
    challenger beats it on energy by the margin."""
    cfg = SwitchConfig(budget_pct=0.5, margin_pct=50.0, min_dwell=1,
                       smooth=1.0)
    state = ControllerState(incumbent="agg")
    scores = {"agg": (2.0, 10.0), "mild": (0.1, 11.0)}   # mild saves LESS
    state, switched, reason = decide(state, scores, cfg)
    assert switched and reason == "over-budget"
    assert state.incumbent == "mild"


def test_controller_no_feasible_keeps_incumbent():
    cfg = SwitchConfig(budget_pct=0.1, min_dwell=1)
    state = ControllerState(incumbent="agg")
    state, switched, reason = decide(
        state, {"agg": (5.0, 10.0), "mild": (3.0, 20.0)}, cfg)
    assert not switched and reason == "no-feasible"
    assert state.incumbent == "agg"


def test_controller_rejects_unknown_incumbent():
    with pytest.raises(AssertionError, match="incumbent"):
        decide(ControllerState(incumbent="ghost"), {"a": (0.0, 1.0)},
               SwitchConfig())


# ---------------------------------------------------------------------------
# Window replay == serial simulate_trace (bit-identity)
# ---------------------------------------------------------------------------


def test_window_replay_bit_identical_to_serial(tiny):
    """The batched lanes the advisor races are the SAME numbers a serial
    ``simulate_trace`` of that window produces — exact ``==``, the sweep
    engine's equivalence contract extended to streaming windows."""
    spec = get_drift("drift-dc-diurnal").scaled(n_nodes=8, windows=2)
    trace = window_trace(spec, tiny, 1)
    lanes = dict(POOL, none=Policy(kind="none"),
                 forecast=Policy(kind="predict", t_pdt=1e-5, t_dst=2e-4,
                                 sleep_state="fast_wake",
                                 deep_state="deep_sleep",
                                 forecast_weight=0.5, forecast_margin=2.0))
    with wavefront_mode("prefix"):
        swept = sweep_cells({trace.name: trace}, tiny,
                            {trace.name: lanes}, PM)[trace.name]
        for name, pol in lanes.items():
            serial, _ = S.simulate_trace(trace, tiny, pol, PM)
            got = swept[name]
            assert got.makespan == serial.makespan, name
            assert got.link_energy == serial.link_energy, name
            assert got.total_energy == serial.total_energy, name
            assert got.mean_latency == serial.mean_latency, name


# ---------------------------------------------------------------------------
# The online loop: warm path + stationarity + the acceptance gate
# ---------------------------------------------------------------------------


def test_stream_acceptance_beats_best_static(tiny):
    """ISSUE 10 acceptance: on a drifting dc-* stream the online advisor
    saves strictly more link energy than the best single static policy in
    hindsight, stays within the degradation budget, and re-advises every
    warm window with ZERO compiles."""
    spec = get_drift("drift-dc-regimes").scaled(n_nodes=8, windows=10)
    out = advise_stream(spec, tiny, pool=POOL, budget_pct=0.1,
                        min_dwell=1, pm=PM)
    t = out["totals"]
    assert t["gain_vs_static_pct"] > 0.0           # strictly beats static
    assert t["online_saved_pct"] > t["best_static_saved_pct"]
    assert t["online_overhead_pct"] <= 0.1         # within budget
    assert out["switches"] >= 2                    # it actually adapted
    # warm-path contract: only window 0 compiles
    compiles = [r["compiles"] for r in out["timeline"]]
    assert all(c == 0 for c in compiles[1:]), compiles
    # the loop is causal: window w is served by the incumbent chosen
    # after window w-1
    for prev, row in zip(out["timeline"], out["timeline"][1:]):
        assert row["incumbent"] == prev["next_incumbent"]


@settings(max_examples=3, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_stream_stationary_traffic_never_flaps(tiny, seed):
    """Stationary arrivals (rate_lo == rate_hi): whatever the seed's
    Poisson noise, the advisor settles and never flaps — at most one
    switch away from the initial incumbent."""
    from repro.scenarios.spec import params_of
    spec = DriftSpec("stationary", "regimes", n_nodes=8, seed=seed,
                     windows=6,
                     params=params_of(rate_lo=800.0, rate_hi=800.0))
    out = advise_stream(spec, tiny, pool=POOL, budget_pct=1.0, pm=PM)
    assert out["switches"] <= 1
    compiles = [r["compiles"] for r in out["timeline"]]
    assert all(c == 0 for c in compiles[1:]), compiles


def test_stream_timeline_shape_and_report(tiny):
    spec = get_drift("drift-dc-flash").scaled(n_nodes=8, windows=4)
    out = advise_stream(spec, tiny, pool=POOL, budget_pct=0.5, pm=PM)
    assert out["windows"] == 4 and len(out["timeline"]) == 4
    assert out["pool"] == list(POOL)
    assert set(out["static_totals"]) == set(POOL)
    for row in out["timeline"]:
        assert row["incumbent"] in POOL
        assert np.isfinite(row["rate"]) and row["rate"] > 0
    # best-static fallback: some candidate (or the baseline) always wins
    assert out["totals"]["best_static"] in (*POOL, "baseline")


def test_advise_stream_front_door(tiny):
    """The launch-layer wrapper resolves catalog names and scales."""
    from repro.launch.power_advisor import advise_stream as front
    out = front("drift-dc-regimes", budget_pct=0.1, topo=tiny, n_nodes=8,
                windows=3, pool=POOL)
    assert out["stream"] == "drift-dc-regimes" and out["windows"] == 3


# ---------------------------------------------------------------------------
# The report's per-window host layers and counters
# ---------------------------------------------------------------------------

HOST_KEYS = {"synth", "lower", "dispatch", "wait", "readback", "summarize",
             "decide"}


@pytest.fixture(scope="module")
def spied(tiny):
    """One stream on fresh window traces, with each window's span log, the
    arrays its replays return and the plans it lowers, window by window."""
    import contextlib

    from repro.core import replay, sweep
    from repro.streaming import online
    spec = get_drift("drift-dc-diurnal").scaled(n_nodes=8, windows=3,
                                                seed=9173)
    logs, replays, plans = [], [], []
    real_log, real_trace = online.span_log, online.window_trace
    real_replay, real_plan = replay.replay_plans, sweep.compile_plan

    @contextlib.contextmanager
    def span_log():
        with real_log() as log:
            logs.append(log)
            yield log

    def window_trace(*a):
        replays.append([])
        plans.append([])
        return real_trace(*a)

    def replay_plans(*a):
        out = real_replay(*a)
        replays[-1].append(out)
        return out

    def compile_plan(*a, **kw):
        plans[-1].append(real_plan(*a, **kw))
        return plans[-1][-1]

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(online, "span_log", span_log)
        mp.setattr(online, "window_trace", window_trace)
        mp.setattr(replay, "replay_plans", replay_plans)
        mp.setattr(sweep, "compile_plan", compile_plan)
        out = advise_stream(spec, tiny, pool=POOL, budget_pct=0.1, pm=PM)
    return spec, out, logs, replays, plans


def test_stream_rows_carry_host_ms_by_layer(spied):
    """Every window reports its seven host layers, each >= 0, and together
    no more than the window's own wall time (its root span)."""
    _, out, logs, _, _ = spied
    assert len(logs) == len(out["timeline"]) == 3
    for row, log in zip(out["timeline"], logs):
        assert set(row["host_ms"]) == HOST_KEYS
        assert all(v >= 0 for v in row["host_ms"].values())
        root = log.spans[0]
        assert root.name == "repro.stream.window" and root.parent is None
        assert all(s.parent is not None for s in log.spans[1:])
        assert sum(row["host_ms"].values()) \
            <= 1e-6 * (root.end_ns - root.start_ns)
        assert row["host_ms"]["dispatch"] > 0 and row["host_ms"]["wait"] > 0
    assert [r["compiles"] for r in out["timeline"][1:]] == [0, 0]


def test_stream_counts_match_window_arrays(spied):
    """``readback_bytes`` is the bytes of every array the window's replays
    bring to host: each lane's end time, latency sums and ``LANE_SUMS``
    row, never its net state; ``placed_bytes`` the plan bytes its lowering
    placed."""
    from repro.traffic.plan import plan_nbytes
    _, out, _, replays, plans = spied
    for row, outs, ps in zip(out["timeline"], replays, plans):
        assert len(outs) == 3            # static groups: none, fixed, dual
        # per lane: t_end, lat_sum, lat_max and its LANE_SUMS, float64
        want = sum(8 * o[1].size * (3 + len(S.LANE_SUMS)) for o in outs)
        assert row["counts"]["readback_bytes"] == want > 0
        assert len(ps) == 1
        assert row["counts"]["placed_bytes"] == plan_nbytes(ps[0]) > 0


def test_stream_places_each_groups_params_once(tiny):
    """``params_placed``: each static group of the replayed lanes places
    its stacked parameters in the first window, and no later window
    places any."""
    from repro.core import replay
    from repro.core.sweep import group_policies
    spec = get_drift("drift-dc-diurnal").scaled(n_nodes=8, windows=2,
                                                seed=9173)
    replay._PARAMS_CACHE.clear()
    out = advise_stream(spec, tiny, pool=POOL, budget_pct=0.1, pm=PM)
    groups = len(group_policies({"baseline": Policy(kind="none"), **POOL}))
    assert groups == 3                   # none, fixed, dual
    assert [r["counts"]["params_placed"] for r in out["timeline"]] \
        == [groups, 0]


def test_stream_report_repeats_but_for_timing(spied, tiny):
    """A second run on the same stream, its caches emptied, reports the
    same numbers in every field but the host times (and no compile) and
    the parameter placements, which count the state of a cache that the
    whole process shares."""
    from repro.streaming.drift import window_cache_clear
    from repro.traffic.plan import plan_cache_clear
    spec, first, _, _, _ = spied
    window_cache_clear()
    plan_cache_clear()
    again = advise_stream(spec, tiny, pool=POOL, budget_pct=0.1, pm=PM)

    def fields(out):
        return dict(out, timeline=[
            {k: v for k, v in dict(r, counts={
                c: n for c, n in r["counts"].items()
                if c != "params_placed"}).items()
             if k not in ("host_ms", "compiles")}
            for r in out["timeline"]])
    assert fields(again) == fields(first)
    assert all(r["compiles"] == 0 for r in again["timeline"])


def test_stream_report_prints_readvice_and_slowest_layer(spied, capsys):
    from repro.launch.power_advisor import print_stream_report
    _, out, _, _, _ = spied
    print_stream_report(out)
    lines = capsys.readouterr().out.splitlines()
    assert "re-advice ms" in lines[2] and "slowest" in lines[2]
    for r, line in zip(out["timeline"], lines[3:]):
        host = r["host_ms"]
        assert f"{sum(host.values()):12.1f}" in line
        assert f" {max(host, key=host.get)} " in line
