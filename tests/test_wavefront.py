"""Wavefront message-phase replay (DESIGN.md §10).

Three contracts:

* the plan-time wave schedule is a valid level schedule — it partitions
  each step's valid messages, waves are link-disjoint, and conflicting
  pairs land in waves that strictly follow their slot order;
* wavefront replay is ``==`` (bit-identical, not allclose) to the serial
  compiled executor across all nine policy kinds x Megafly + fat-tree —
  reordering commuting link-disjoint updates introduces ZERO numerical
  drift — and matches the step-loop reference at the equivalence suite's
  standard tolerance (the compiled serial path itself differs from the
  host reference by ~1 ulp in latency accumulation order, a pre-existing
  slop test_plan.py pins at rtol 1e-9);
* warm wavefront replays stay device-resident: 0 compiles, 0 transfers.
"""
import jax
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import replay
from repro.core import simulator as S
from repro.core.eee import Policy, PowerModel
from repro.core.instrument import count_compiles
from repro.topology.fattree import small_fattree
from repro.topology.megafly import small_topology
from repro.traffic import plan as P
from repro.traffic.trace import Trace

from test_plan import (CHECK_FIELDS, POLICIES, TOPOS, _assert_results_match,
                       traces)

PM = PowerModel()


def _assert_bit_identical(got, want, label=""):
    g, w = got.as_dict(), want.as_dict()
    for k in CHECK_FIELDS:
        assert np.asarray(g[k] == w[k]).all(), \
            f"{label}.{k}: {g[k]!r} != {w[k]!r}"


# ---------------------------------------------------------------------------
# Wave schedule properties (host twins of the executor's in-step pass)
# ---------------------------------------------------------------------------


@st.composite
def step_routes(draw):
    """Random per-step route sets: M messages x up to H hops over a small
    link id space (dense enough to exercise real conflicts)."""
    m = draw(st.integers(min_value=1, max_value=12))
    h = draw(st.integers(min_value=1, max_value=4))
    links = np.full((m, h), -1, np.int64)
    nhops = np.zeros((m,), np.int64)
    for i in range(m):
        nhops[i] = draw(st.integers(1, h))
        for j in range(int(nhops[i])):
            links[i, j] = draw(st.integers(0, 6))
    return links, nhops


@settings(max_examples=50, deadline=None)
@given(data=st.data())
def test_wave_schedule_is_valid(data):
    links, nhops = data.draw(step_routes())
    m = links.shape[0]
    conf = P.step_conflicts(links, nhops)
    wave = P.wave_assign(conf)

    # partition: every message gets exactly one wave id in [1, W]
    W = int(wave.max())
    assert wave.shape == (m,)
    assert (wave >= 1).all() and (wave <= W).all()
    for w in range(1, W + 1):
        assert (wave == w).any(), f"empty wave {w}"

    # link-disjoint: no conflicting pair shares a wave
    same = wave[:, None] == wave[None, :]
    assert not (conf & same).any(), "conflicting pair in one wave"

    # ordering contract: conflicting pairs keep slot order across waves
    i, j = np.nonzero(conf & (np.arange(m)[:, None] < np.arange(m)[None, :]))
    assert (wave[i] < wave[j]).all(), "wave order violates slot order"


@settings(max_examples=50, deadline=None)
@given(data=st.data())
def test_device_conflicts_match_host(data):
    """The executor's on-device conflict matrix == the planner's host one."""
    links, nhops = data.draw(step_routes())
    m = links.shape[0]
    valid = np.ones((m,), bool)
    dev = np.asarray(replay._conflicts(
        np.asarray(links), np.asarray(nhops), valid))
    np.testing.assert_array_equal(dev, P.step_conflicts(links, nhops))


def test_wave_width_counterexamples():
    """Pinned cases: the order-preserving recurrence is NOT graph
    coloring — a conflict path goes fully serial (width > maxdeg+1), an
    independent pairing pipelines at width 2, and disjoint routes
    collapse to one wave."""
    # path a-b, b-c, c-d: each message waits on its predecessor, so every
    # edge forces a new wave — width 4 > chromatic 2, > maxdeg+1 == 3
    conf = P.step_conflicts(
        np.asarray([[0, 1], [1, 2], [2, 3], [3, 4]]),
        np.asarray([2, 2, 2, 2]))
    assert int(P.wave_assign(conf).max()) == 4
    assert int(P.wave_assign(conf[::-1][:, ::-1]).max()) == 4
    # two independent conflicting pairs interleave: width 2
    conf_p = P.step_conflicts(
        np.asarray([[0], [0], [1], [1]]), np.asarray([1, 1, 1, 1]))
    np.testing.assert_array_equal(P.wave_assign(conf_p), [1, 2, 1, 2])
    # disjoint links: single wave
    conf_d = P.step_conflicts(
        np.asarray([[0], [1], [2], [3]]), np.asarray([1, 1, 1, 1]))
    assert int(P.wave_assign(conf_d).max()) == 1


def test_plan_wave_metadata():
    """Segment wave/live metadata drives the executor's mode choice."""
    topo = TOPOS["megafly"]
    nodes = np.arange(8, dtype=np.int64)
    tr = Trace(nodes=nodes)
    tr.messages([[0, 1, 512]])                       # 1 msg: width 1
    tr.messages([[int(a), int(b), 512] for a in range(8) for b in range(8)
                 if a != b], barrier=True)           # alltoall: wide step
    plan = P.compile_plan(tr, topo)
    caps = {s.cap for s in plan.segments}
    assert all(c > 0 for c in caps)
    seg_small = plan.segments[0]
    assert seg_small.host_wave is not None
    ww = [s.wave_width for s in plan.segments]
    assert max(ww) >= 2                              # conflicts exist
    assert all(1 <= w <= s.cap
               for w, s in zip(ww, plan.segments) if s.cap)
    for s in plan.segments:
        if not s.cap:
            continue
        # the prefix executor's trip counts ride in the device arrays and
        # agree with the host metadata the cost model reads
        np.testing.assert_array_equal(np.asarray(s.xs["live"]),
                                      s.host_live)
        assert 0.0 < s.mean_live <= s.cap
        assert 1.0 <= s.mean_wave <= s.wave_width
        # cost model: mostly-padding steps must never keep the full scan
        costs = replay.phase_costs(s, Policy(kind="fixed", t_pdt=1e-5))
        assert set(costs) == {"scan", "prefix", "chain"}
        if s.mean_live * 4 <= s.cap:
            assert min(costs, key=costs.get) != "scan"
    # needs_sort flags steps with >1 live messages
    assert any(s.needs_sort for s in plan.segments)
    # single-message-per-step segment: sort skipped
    tr2 = Trace(nodes=nodes)
    tr2.messages([[0, 1, 512]])
    tr2.messages([[2, 3, 512]], barrier=True)
    plan2 = P.compile_plan(tr2, topo)
    assert all(not s.needs_sort for s in plan2.segments if s.cap)
    assert all(s.wave_width <= 1 for s in plan2.segments if s.cap)
    # adaptive kinds never get the chained lowering offered
    costs = replay.phase_costs(plan.segments[0],
                               Policy(kind="perfbound", bound=0.01))
    assert "chain" not in costs


# ---------------------------------------------------------------------------
# Equivalence: wavefront replay == step-loop reference, bit-identical
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("topo_name", list(TOPOS))
@pytest.mark.parametrize("kind", list(POLICIES))
@settings(max_examples=2, deadline=None)
@given(data=st.data())
def test_wavefront_replay_bit_identical(topo_name, kind, data):
    topo = TOPOS[topo_name]
    tr = data.draw(traces(topo.n_nodes))
    pol = POLICIES[kind]
    with replay.wavefront_mode("off"):
        serial, _ = S.simulate_trace(tr, topo, pol, PM)
    # force BOTH plan-scheduled lowerings — the heuristic modes pick
    # between these, so pinning each pins all of on/auto too
    for mode in ("prefix", "chain"):
        with replay.wavefront_mode(mode):
            got, _ = S.simulate_trace(tr, topo, pol, PM)
        # the new invariant: the lowering reorders NOTHING numerically
        _assert_bit_identical(got, serial, f"{topo_name}/{kind}/{mode}")
    # and the oracle contract the serial path already carries
    want, _ = S.simulate_trace_reference(tr, topo, pol, PM)
    _assert_results_match(got, want, f"{topo_name}/{kind}")


@settings(max_examples=2, deadline=None)
@given(data=st.data())
def test_wavefront_modes_agree(data):
    """Every mode produces the same bits (mode is perf-only), including
    the heuristic ones, for an adaptive kind (fallback wave loop)."""
    topo = TOPOS["fattree"]
    tr = data.draw(traces(topo.n_nodes))
    pol = POLICIES["perfbound_dual"]
    outs = {}
    for mode in replay.WAVEFRONT_MODES:
        with replay.wavefront_mode(mode):
            outs[mode], _ = S.simulate_trace(tr, topo, pol, PM)
    for mode in replay.WAVEFRONT_MODES:
        _assert_bit_identical(outs[mode], outs["off"], f"{mode}-vs-off")


def test_wavefront_multi_trace_grid():
    """The (T, B) PlanBatch path rides the same wavefront programs.

    The B lanes must share ONE static group (``canonical_proto`` comes
    from lane 0), so vary the fixed kind's timer instead of the kind."""
    topo = TOPOS["megafly"]
    pols = [Policy(kind="fixed", t_pdt=t) for t in (2e-6, 5e-6, 2e-5)]
    trs = []
    for r in (1, 3):
        nodes = np.arange(10, dtype=np.int64)
        tr = Trace(nodes=nodes, name=f"t{r}")
        tr.compute(1e-4)
        tr.messages([[int(i), int((i + r) % 10), 2048] for i in range(10)],
                    barrier=True)
        trs.append(tr)
    plans = [P.compile_plan(t, topo) for t in trs]
    batch = P.stack_plans(plans)
    with replay.wavefront_mode("on"):
        _, t_end, lat_sum, lat_max = replay.replay_plans(batch, pols, PM)
    for ti, tr in enumerate(trs):
        for bi, pol in enumerate(pols):
            with replay.wavefront_mode("off"):
                want, _ = S.simulate_trace(tr, topo, pol, PM)
            w = want.as_dict()
            assert t_end[ti, bi] == w["makespan"]
            assert lat_max[ti, bi] == w["max_latency"]


@pytest.mark.parametrize("mode", ["scan", "prefix", "chain"])
def test_replay_programs_are_named_by_executor(mode):
    """Each segment program is named after its executor, so a device trace
    reads ``jit_replay_<mode>`` for it, on the single- and multi-trace
    runners alike."""
    topo = TOPOS["megafly"]
    tr = Trace(nodes=np.arange(6, dtype=np.int64))
    tr.messages([[i, (i + 1) % 6, 2048] for i in range(6)], barrier=True)
    plan = P.compile_plan(tr, topo)
    seg = next(s for s in plan.segments if s.cap)
    pols = [Policy(kind="fixed", t_pdt=2e-6)]
    proto, params, carry = replay.init_lanes(pols, plan)
    run = replay._segment_runner(proto, PM, plan.n_links, seg.cap, False,
                                 mode)
    text = run.lower(carry[0], params, carry[1], carry[2], carry[3],
                     plan.part_mask, seg.xs).as_text()
    assert f"module @jit_replay_{mode}" in text
    batch = P.stack_plans([plan])
    proto, params, carry = replay.init_lanes_multi(pols, batch)
    run = replay._multi_segment_runner(proto, PM, batch.n_links, seg.cap,
                                       mode)
    text = run.lower(carry[0], params, carry[1], carry[2], carry[3],
                     batch.part_mask,
                     next(s for s in batch.segments if s.cap).xs).as_text()
    assert f"module @jit_replay_{mode}" in text


@pytest.mark.parametrize("T", [1, 2])
def test_trace_stack_updates_histograms_in_place(T):
    """The multi-trace ``prefix`` program of a keep_all PerfBound lane
    selects no histogram whole: its loop bound is reduced over the traces
    before the trace vmap and its steps take no message branch, so nothing
    lowers to a select between old and new carries that would make XLA
    copy the histograms before each scatter."""
    topo = TOPOS["fattree"]
    pol = Policy(kind="perfbound", bound=0.01, hist_mode="keep_all",
                 sleep_state="deep_sleep")
    plans = []
    for r in range(1, T + 1):
        tr = Trace(nodes=np.arange(8, dtype=np.int64), name=f"t{r}")
        tr.compute(1e-5)
        tr.messages([[i, (i + r) % 8, 2048] for i in range(8)],
                    barrier=True)
        plans.append(P.compile_plan(tr, topo))
    batch = P.stack_plans(plans)
    seg = next(s for s in batch.segments if s.cap)
    proto, params, carry = replay.init_lanes_multi([pol], batch)
    run = replay._multi_segment_runner(proto, PM, batch.n_links, seg.cap,
                                       "prefix", seg.needs_sort)
    text = run.lower(carry[0], params, carry[1], carry[2], carry[3],
                     batch.part_mask, replay.segment_xs(seg, proto)).as_text()
    hist = f"x{topo.n_links + 1}x{pol.hist_bins}xf64>"
    assert carry[0]["pred"]["counts"].shape[-2:] \
        == (topo.n_links + 1, pol.hist_bins)
    assert "stablehlo.while" in text and "stablehlo.scatter" in text
    selects = [ln for ln in text.splitlines()
               if "stablehlo.select" in ln and ln.rstrip().endswith(hist)]
    assert selects == []


# ---------------------------------------------------------------------------
# Device residency: warm wavefront replay = 0 compiles, 0 transfers
# ---------------------------------------------------------------------------


def test_warm_wavefront_replay_is_device_resident():
    topo = TOPOS["megafly"]
    nodes = np.arange(12, dtype=np.int64)
    tr = Trace(nodes=nodes)
    for r in range(3):
        tr.compute(1e-4)
        tr.messages([[int(i), int((i + 1 + r) % 12) , 4096]
                     for i in range(12)], barrier=(r == 2))
    pol = Policy(kind="perfbound", bound=0.01)
    plan = P.compile_plan(tr, topo)

    with replay.wavefront_mode("on"):
        proto, params, carry = replay.init_lanes([pol], plan)
        out = replay.run_segments(plan, proto, params, PM, carry)  # cold
        warm_t_end = float(out[1][0])

        proto, params, carry = replay.init_lanes([pol], plan)
        with count_compiles() as cc, jax.transfer_guard("disallow"):
            out = replay.run_segments(plan, proto, params, PM, carry)
        assert cc.count == 0, "warm wavefront replay recompiled"
        t_end = float(out[1][0])
        assert t_end == warm_t_end > 0.0
