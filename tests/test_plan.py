"""Plan/execute pipeline: property-style equivalence with the step-loop
reference engine (all nine policy kinds — incl. the dual-mode FSM ladder,
coalescing and the predictive FSMs — x FatTree + Megafly, including
collect_events), plan
lowering/segmentation, plan + route caches, and device-residency of the
hot loop (no transfers, no warm compiles)."""
import jax
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import replay
from repro.core import simulator as S
from repro.core.eee import Policy, PowerModel
from repro.core.instrument import count_compiles
from repro.core.sweep import sweep_policies
from repro.topology.fattree import small_fattree
from repro.topology.megafly import small_topology
from repro.traffic import plan as P
from repro.traffic.trace import Trace

PM = PowerModel()
TOPOS = {"megafly": small_topology(), "fattree": small_fattree(4)}

POLICIES = {
    "none": Policy(kind="none"),
    "fixed": Policy(kind="fixed", t_pdt=5e-5, sleep_state="deep_sleep"),
    "perfbound": Policy(kind="perfbound", bound=0.02,
                        sleep_state="fast_wake"),
    "perfbound_correct": Policy(kind="perfbound_correct", bound=0.01,
                                hist_mode="circular", ring_n=32),
    "dual": Policy(kind="dual", t_pdt=2e-5, t_dst=2e-4,
                   sleep_state="fast_wake", deep_state="deep_sleep"),
    "coalesce": Policy(kind="coalesce", t_pdt=2e-5, t_dst=2e-4,
                       max_delay=5e-5, max_frames=4,
                       sleep_state="fast_wake", deep_state="deep_sleep"),
    "perfbound_dual": Policy(kind="perfbound_dual", bound=0.02,
                             sleep_state="fast_wake",
                             deep_state="deep_sleep"),
    "precoalesce": Policy(kind="precoalesce", t_pdt=2e-5, t_dst=2e-4,
                          hold_delay=5e-5, hold_frames=4,
                          sleep_state="fast_wake", deep_state="deep_sleep"),
    "predict": Policy(kind="predict", t_pdt=2e-5, t_dst=2e-4,
                      forecast_weight=0.5, forecast_margin=2.0,
                      sleep_state="fast_wake", deep_state="deep_sleep"),
}

CHECK_FIELDS = ("makespan", "mean_latency", "max_latency", "n_messages",
                "link_energy", "switch_energy", "node_energy", "total_energy",
                "asleep_frac", "deep_frac", "n_wake_transitions", "hits", "misses",
                "deep_misses")


def _assert_results_match(got, want, label=""):
    g, w = got.as_dict(), want.as_dict()
    for k in CHECK_FIELDS:
        np.testing.assert_allclose(g[k], w[k], rtol=1e-9, atol=1e-12,
                                   err_msg=f"{label}.{k}")


@st.composite
def traces(draw, n_total):
    """Random phase-structured traces: compute / message / barrier steps in
    arbitrary interleavings (incl. consecutive computes, barrier-only steps,
    and messages-with-barrier — every lowering/fusion path)."""
    n = draw(st.integers(min_value=2, max_value=8))
    ids = draw(st.lists(st.integers(0, n_total - 1), min_size=n,
                        max_size=n, unique=True))
    nodes = np.asarray(sorted(ids), np.int64)
    tr = Trace(nodes=nodes, name="prop")
    for _ in range(draw(st.integers(min_value=2, max_value=6))):
        op = draw(st.sampled_from(
            ["compute", "compute", "msgs", "msgs", "msgs_barrier",
             "barrier"]))
        if op == "compute":
            tr.compute(np.asarray(
                [draw(st.floats(1e-6, 2e-3)) for _ in range(n)]))
        elif op == "barrier":
            tr.barrier()
        else:
            m = draw(st.integers(min_value=1, max_value=10))
            msgs = [[int(nodes[draw(st.integers(0, n - 1))]),
                     int(nodes[draw(st.integers(0, n - 1))]),
                     draw(st.integers(64, 1 << 14))] for _ in range(m)]
            tr.messages(msgs, barrier=op == "msgs_barrier")
    tr.messages([[int(nodes[0]), int(nodes[-1]), 1024]], barrier=True)
    return tr


# ---------------------------------------------------------------------------
# Equivalence: compiled plan replay == step-loop reference replay
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("topo_name", list(TOPOS))
@pytest.mark.parametrize("kind", list(POLICIES))
@settings(max_examples=3, deadline=None)
@given(data=st.data())
def test_compiled_replay_matches_step_loop(topo_name, kind, data):
    topo = TOPOS[topo_name]
    tr = data.draw(traces(topo.n_nodes))
    pol = POLICIES[kind]
    want, _ = S.simulate_trace_reference(tr, topo, pol, PM)
    got, _ = S.simulate_trace(tr, topo, pol, PM)
    _assert_results_match(got, want, f"{topo_name}/{kind}")


def _pinned_trace(nodes, steps):
    tr = Trace(nodes=np.asarray(nodes, np.int64), name="pinned")
    for op, arg in steps:
        if op == "compute":
            tr.compute(np.asarray(arg))
        else:
            tr.messages(arg, barrier=op == "msgs_barrier")
    return tr


# Draws that once split the two engines for the adaptive kinds: a gap of
# exactly one histogram bin width (1e-5 s), which the step loop binned one
# bin lower while its policy numerics were compile-time constants.
PINNED_TRACES = {
    "selfmsg-after-compute": ([0, 1], [
        ("compute", [1e-5, 1.953125e-3]), ("msgs", [[0, 0, 64]]),
        ("msgs_barrier", [[0, 1, 1024]])]),
    "selfmsg-before-compute": ([0, 1], [
        ("msgs", [[0, 0, 64]]), ("compute", [1e-5, 1.953125e-3]),
        ("msgs_barrier", [[0, 1, 1024]])]),
    "fan-in-then-compute": ([0, 1, 2, 4], [
        ("msgs", [[1, 0, 64], [4, 0, 64], [0, 0, 64]]),
        ("compute", [1e-5, 1.953125e-3, 1.953125e-3, 1.953125e-3]),
        ("msgs_barrier", [[0, 4, 1024]])]),
    "one-short-node": (list(range(8)), [
        ("compute", [1.953125e-3] * 7 + [1e-5]), ("msgs", [[7, 0, 64]]),
        ("msgs_barrier", [[0, 7, 1024]])]),
}


@pytest.mark.parametrize("topo_name", list(TOPOS))
@pytest.mark.parametrize("kind", ["perfbound", "perfbound_correct",
                                  "perfbound_dual", "predict"])
@pytest.mark.parametrize("trace_name", list(PINNED_TRACES))
def test_compiled_replay_matches_step_loop_pinned(topo_name, kind,
                                                  trace_name):
    topo = TOPOS[topo_name]
    tr = _pinned_trace(*PINNED_TRACES[trace_name])
    pol = POLICIES[kind]
    want, _ = S.simulate_trace_reference(tr, topo, pol, PM)
    got, _ = S.simulate_trace(tr, topo, pol, PM)
    _assert_results_match(got, want, f"{topo_name}/{kind}/{trace_name}")


@pytest.mark.parametrize("topo_name", list(TOPOS))
@settings(max_examples=3, deadline=None)
@given(data=st.data())
def test_collect_events_matches_step_loop(topo_name, data):
    topo = TOPOS[topo_name]
    tr = data.draw(traces(topo.n_nodes))
    pol = POLICIES["fixed"]
    want, ev_want = S.simulate_trace_reference(tr, topo, pol, PM,
                                               collect_events=True)
    got, ev_got = S.simulate_trace(tr, topo, pol, PM, collect_events=True)
    _assert_results_match(got, want, topo_name)
    assert len(ev_got) == len(ev_want)
    for a, b in zip(ev_want, ev_got):
        for x, y in zip(a, b):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@settings(max_examples=2, deadline=None)
@given(data=st.data())
def test_batched_sweep_matches_step_loop(data):
    """The batched plan executor (B policy lanes, per-lane device argsort)
    reproduces the step-loop reference for a mixed-kind grid."""
    topo = TOPOS["megafly"]
    tr = data.draw(traces(topo.n_nodes))
    grid = {
        "none": Policy(kind="none"),
        "fw": Policy(kind="fixed", t_pdt=1e-5, sleep_state="fast_wake"),
        "ds": Policy(kind="fixed", t_pdt=1e-4, sleep_state="deep_sleep"),
        "pb1": Policy(kind="perfbound", bound=0.01),
        "pb5": Policy(kind="perfbound", bound=0.05),
        "pbc": Policy(kind="perfbound_correct", bound=0.02),
        "dual": Policy(kind="dual", t_pdt=1e-5, t_dst=1e-4,
                       sleep_state="fast_wake", deep_state="deep_sleep"),
        "coal": Policy(kind="coalesce", t_pdt=1e-5, t_dst=1e-4,
                       max_delay=2e-5, max_frames=4,
                       sleep_state="fast_wake", deep_state="deep_sleep"),
        "pbd": Policy(kind="perfbound_dual", bound=0.02,
                      sleep_state="fast_wake", deep_state="deep_sleep"),
        "pre": Policy(kind="precoalesce", t_pdt=1e-5, t_dst=1e-4,
                      hold_delay=2e-5, hold_frames=4,
                      sleep_state="fast_wake", deep_state="deep_sleep"),
        "pred": Policy(kind="predict", t_pdt=1e-5, t_dst=1e-4,
                       forecast_weight=0.5, forecast_margin=2.0,
                       sleep_state="fast_wake", deep_state="deep_sleep"),
    }
    out = sweep_policies(tr, topo, grid, PM)
    for name, pol in grid.items():
        want, _ = S.simulate_trace_reference(tr, topo, pol, PM)
        _assert_results_match(out[name], want, name)


# ---------------------------------------------------------------------------
# Lowering + segmentation
# ---------------------------------------------------------------------------


def test_lowering_fuses_phases():
    """compute-only fuses into the NEXT message step; a trailing barrier
    folds into the PREVIOUS plan step — one plan step, one segment."""
    tr = Trace(nodes=np.arange(4, dtype=np.int64))
    tr.compute(1e-3).messages([[0, 1, 256]]).barrier()
    plan = P.compile_plan(tr, small_topology())
    assert plan.n_steps == 1 and plan.n_message_steps == 1
    [seg] = plan.segments
    assert seg.cap == P.BUCKET_MIN
    assert bool(np.asarray(seg.xs["barrier"])[0])
    assert float(np.asarray(seg.xs["delta"]).sum()) == pytest.approx(4e-3)


def test_segmentation_by_bucket():
    """Message steps land in power-of-two buckets; a bucket change starts
    a new segment, message-less steps join the current one."""
    topo = small_topology()
    nodes = np.arange(16, dtype=np.int64)
    tr = Trace(nodes=nodes)
    small = [[int(i), int((i + 1) % 16), 512] for i in range(5)]
    big = [[int(i % 16), int((i + 7) % 16), 512] for i in range(200)]
    tr.messages(small).compute(1e-3).messages(small)
    tr.messages(big)
    tr.messages(small, barrier=True)
    plan = P.compile_plan(tr, topo)
    assert [s.cap for s in plan.segments] == [64, 256, 64]
    assert plan.n_msgs == 5 + 5 + 200 + 5
    assert P.bucket_cap(5) == 64 and P.bucket_cap(200) == 256


def test_compute_only_trace_runs():
    tr = Trace(nodes=np.arange(4, dtype=np.int64))
    tr.compute(np.array([1.0, 2.0, 0.5, 0.1])).barrier().compute(1.0)
    plan = P.compile_plan(tr, small_topology())
    assert all(s.cap == 0 for s in plan.segments)
    res, _ = S.simulate_trace(tr, small_topology(), Policy(kind="none"), PM)
    np.testing.assert_allclose(res.makespan, 3.0, rtol=1e-12)


# ---------------------------------------------------------------------------
# Caches
# ---------------------------------------------------------------------------


def test_plan_cache_hits_and_invalidates():
    topo = small_topology()
    tr = Trace(nodes=np.arange(4, dtype=np.int64))
    tr.messages([[0, 1, 512]], barrier=True)
    p1 = P.compile_plan(tr, topo)
    assert P.compile_plan(tr, topo) is p1          # sweep groups share it
    assert P.compile_plan(tr, TOPOS["fattree"]) is not p1
    tr.messages([[1, 2, 512]], barrier=True)       # builder mutation
    p2 = P.compile_plan(tr, topo)
    assert p2 is not p1 and p2.n_msgs == 2


def test_route_cache_returns_shared_arrays():
    for topo in TOPOS.values():
        topo.clear_route_cache()
        src = np.arange(8, dtype=np.int64)
        dst = (src + 5) % topo.n_nodes
        a = topo.routes_cached(src, dst)
        b = topo.routes_cached(src, dst)
        assert all(x is y for x, y in zip(a, b))   # cache hit: same arrays
        for x, y in zip(a, topo.routes(src, dst)):
            np.testing.assert_array_equal(x, y)
        assert topo.route_cache_info()["entries"] >= 1


# ---------------------------------------------------------------------------
# Device residency: the hot loop neither transfers nor compiles when warm
# ---------------------------------------------------------------------------


def test_warm_replay_is_device_resident():
    topo = TOPOS["megafly"]
    nodes = np.arange(12, dtype=np.int64)
    tr = Trace(nodes=nodes)
    for r in range(3):
        tr.compute(1e-4)
        tr.messages([[int(i), int((i + 1 + r) % 12), 4096] for i in range(12)],
                    barrier=(r == 2))
    pol = Policy(kind="perfbound", bound=0.01)
    plan = P.compile_plan(tr, topo)
    pm = PM

    proto, params, carry = replay.init_lanes([pol], plan)
    out = replay.run_segments(plan, proto, params, pm, carry)  # cold warm-up
    warm_t_end = float(out[1][0])

    proto, params, carry = replay.init_lanes([pol], plan)
    with count_compiles() as cc, jax.transfer_guard("disallow"):
        out = replay.run_segments(plan, proto, params, pm, carry)
    assert cc.count == 0, "warm replay recompiled"
    t_end = float(out[1][0])                       # readback OUTSIDE guard
    assert t_end == warm_t_end > 0.0


# ---------------------------------------------------------------------------
# Wave-schedule plan metadata (DESIGN.md §10) — the planner's contract with
# the wavefront executors, over arbitrary phase-structured traces
# ---------------------------------------------------------------------------


@settings(max_examples=10, deadline=None)
@given(data=st.data())
def test_plan_wave_metadata_contract(data):
    """Every message segment carries live counts and wave widths that are
    host twins of its device arrays: ``host_live`` == per-step valid
    counts (shipped as ``xs["live"]`` for the prefix executor's trip
    bound), ``host_wave`` == the width ``wave_assign`` derives from the
    step's real routes, and the derived ``needs_sort`` / ``wave_width`` /
    ``mean_live`` / ``mean_wave`` flags follow."""
    topo = TOPOS["megafly"]
    tr = data.draw(traces(topo.n_nodes))
    plan = P.compile_plan(tr, topo)
    assert any(s.cap for s in plan.segments)
    for s in plan.segments:
        if not s.cap:
            assert s.needs_sort          # conservative default, never read
            continue
        valid = np.asarray(s.xs["valid"])
        np.testing.assert_array_equal(s.host_live, valid.sum(axis=1))
        np.testing.assert_array_equal(np.asarray(s.xs["live"]), s.host_live)
        links, nhops = np.asarray(s.xs["links"]), np.asarray(s.xs["nhops"])
        for i in range(valid.shape[0]):
            m = int(s.host_live[i])
            if m == 0:
                assert s.host_wave[i] == 0
                continue
            conf = P.step_conflicts(links[i, :m], nhops[i, :m])
            assert s.host_wave[i] == int(P.wave_assign(conf).max())
            assert 1 <= s.host_wave[i] <= m
        assert s.wave_width == int(s.host_wave.max(initial=0))
        assert s.needs_sort == (int(s.host_live.max(initial=0)) > 1)
        if s.host_live.max(initial=0) > 0:
            assert 0.0 < s.mean_live <= s.cap
            assert 1.0 <= s.mean_wave <= max(s.wave_width, 1)
