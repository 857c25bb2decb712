"""Scenario engine: catalog coverage, seeded determinism, plan-shape
stacking, and the multi-trace batched grid — equivalence with the
per-trace engines (compiled serial AND step-loop reference) plus the
program-count bound, in the style of tests/test_plan.py."""
import numpy as np
import pytest

from repro import scenarios as SC
from repro.core import simulator as S
from repro.core.eee import Policy, PowerModel
from repro.core.instrument import count_compiles
from repro.core.sweep import group_policies, sweep_policies, sweep_scenarios
from repro.scenarios.ml import derive_grid
from repro.scenarios.models import get_model
from repro.topology.megafly import small_topology
from repro.traffic import plan as P

PM = PowerModel()
# 12-node Megafly: big enough for 8-node allocations, fast to replay
TINY = small_topology(n_groups=3, leaves=2, spines=2, nodes_per_leaf=2)

DC_NAMES = ["dc-poisson", "dc-hotspot", "dc-onoff", "dc-incast"]

GRID = {
    "fw": Policy(kind="fixed", t_pdt=1e-5, sleep_state="fast_wake"),
    "ds": Policy(kind="fixed", t_pdt=1e-4, sleep_state="deep_sleep"),
    "pb1": Policy(kind="perfbound", bound=0.01),
    "pb5": Policy(kind="perfbound", bound=0.05),
    "dual": Policy(kind="dual", t_pdt=1e-5, t_dst=2e-4,
                   sleep_state="fast_wake", deep_state="deep_sleep"),
    "pbd": Policy(kind="perfbound_dual", bound=0.01,
                  sleep_state="fast_wake", deep_state="deep_sleep"),
}


def _dc_traces(n_nodes=8):
    return {n: SC.build_trace(SC.get_scenario(n).scaled(n_nodes), TINY)
            for n in DC_NAMES}


# ---------------------------------------------------------------------------
# Catalog + determinism
# ---------------------------------------------------------------------------


def test_catalog_coverage():
    names = SC.list_scenarios()
    assert len(names) >= 8
    for family, n_min in (("ml", 2), ("hpc", 2), ("dc", 2)):
        assert len(SC.list_scenarios(family)) >= n_min, family
    for name in names:
        assert SC.get_scenario(name).description


def _steps_equal(a, b):
    assert len(a.steps) == len(b.steps)
    for sa, sb in zip(a.steps, b.steps):
        assert sa.barrier == sb.barrier
        for f in ("compute_nodes", "compute_secs", "msgs"):
            x, y = getattr(sa, f), getattr(sb, f)
            assert (x is None) == (y is None)
            if x is not None:
                assert np.asarray(x).dtype == np.asarray(y).dtype
                np.testing.assert_array_equal(x, y)


@pytest.mark.parametrize("name", sorted(SC.catalog()))
def test_same_seed_same_trace(name):
    """Scenario synthesis is a pure function of (spec, topology): rebuilding
    with the cache cleared reproduces every step bit-identically."""
    spec = SC.get_scenario(name).scaled(8)
    t1 = SC.build_trace(spec, TINY)
    SC.trace_cache_clear()
    t2 = SC.build_trace(spec, TINY)
    assert t1 is not t2
    np.testing.assert_array_equal(t1.nodes, t2.nodes)
    _steps_equal(t1, t2)


def test_seed_changes_stochastic_traces():
    spec = SC.get_scenario("dc-poisson").scaled(8)
    t1 = SC.build_trace(spec, TINY)
    t2 = SC.build_trace(spec.scaled(8, seed=spec.seed + 1), TINY)
    diff = any(
        (a.msgs is None) != (b.msgs is None)
        or (a.msgs is not None and (a.msgs.shape != b.msgs.shape
                                    or not np.array_equal(a.msgs, b.msgs)))
        for a, b in zip(t1.steps, t2.steps)) or len(t1.steps) != len(t2.steps)
    assert diff, "reseeding left the stochastic trace unchanged"


def test_trace_cache_identity():
    """Equal spec values share ONE trace (keeps the plan cache keyed per
    scenario); different values do not."""
    spec = SC.get_scenario("dc-onoff").scaled(8)
    t1 = SC.build_trace(spec, TINY)
    assert SC.build_trace(SC.get_scenario("dc-onoff").scaled(8), TINY) is t1
    assert SC.build_trace(spec.scaled(8, seed=99), TINY) is not t1


def test_incast_fan_in_at_flow_cap():
    """fan_in >= max_flows must not crash (background trickle clamps to
    zero, it cannot go negative) and the fan-in itself survives."""
    spec = SC.Scenario("t-incast-wide", "dc", "incast", 8, seed=7,
                       params=SC.params_of(fan_in=7, max_flows=7,
                                           windows=4))
    tr = SC.build_trace(spec, TINY)
    msg_steps = [s for s in tr.steps if s.msgs is not None]
    assert len(msg_steps) == 4
    assert all(len(s.msgs) == 7 for s in msg_steps)


def test_stochastic_degenerate_params_emit_valid_windows():
    """Satellite audit: degenerate catalog edges — rate -> 0, duty cycle
    pinned to 0/1, an all-hot skew, fan_in <= 0 — must still emit exactly
    one NON-EMPTY message step per window with src != dst (the dc-*
    plan-shape guarantee), not divide by zero or crash the samplers."""
    cases = [
        ("poisson", dict(rate=0.0), 8),
        ("poisson", dict(hot_frac=1.0), 8),
        ("poisson", dict(hot_frac=0.5), 2),     # n_hot clamps below n_nodes
        ("onoff", dict(rate_off=0.0, p_on=0.0), 8),     # duty cycle 0
        ("onoff", dict(p_on=1.0, p_stay_on=1.0), 8),    # duty cycle 1
        ("incast", dict(fan_in=0), 8),
        ("incast", dict(fan_in=0, background_rate=0.0), 8),
    ]
    for i, (builder, extra, n) in enumerate(cases):
        spec = SC.Scenario(f"t-degen-{i}", "dc", builder, n, seed=11,
                           params=SC.params_of(windows=4, **extra))
        tr = SC.build_trace(spec, TINY)
        msg_steps = [s for s in tr.steps if s.msgs is not None]
        assert len(msg_steps) == 4, (builder, extra)
        for s in msg_steps:
            m = np.asarray(s.msgs)
            assert len(m) >= 1, (builder, extra)
            assert np.all(m[:, 0] != m[:, 1]), (builder, extra)


def test_stochastic_degenerates_share_plan_shape():
    """A zero-rate window still occupies one message bucket, so degenerate
    dc variants keep stacking along the multi-trace axis."""
    specs = [SC.Scenario("t-degen-a", "dc", "poisson", 8, seed=5,
                         params=SC.params_of(rate=0.0, windows=6)),
             SC.Scenario("t-degen-b", "dc", "onoff", 8, seed=5,
                         params=SC.params_of(rate_off=0.0, p_on=0.0,
                                             windows=6))]
    plans = [P.compile_plan(SC.build_trace(s, TINY), TINY) for s in specs]
    assert len({P.plan_shape_key(p) for p in plans}) == 1


def test_stochastic_invalid_params_fail_loudly():
    """n_nodes < 2 cannot form src != dst pairs and windows < 1 would
    synthesize an empty trace — both must raise up front, not crash deep
    inside a sampler (or emit a shape-breaking trace)."""
    for builder in ("poisson", "onoff", "incast"):
        with pytest.raises(ValueError, match="n_nodes >= 2"):
            SC.build_trace(SC.Scenario("t-bad-n", "dc", builder, 1, seed=1),
                           TINY)
        with pytest.raises(ValueError, match="windows >= 1"):
            SC.build_trace(
                SC.Scenario("t-bad-w", "dc", builder, 8, seed=1,
                            params=SC.params_of(windows=0)), TINY)


def test_ml_grid_derivation():
    assert derive_grid(8) == (4, 2, 1)
    assert derive_grid(16) == (4, 2, 2)
    assert derive_grid(16, dp=2, tp=4, pp=2) == (2, 4, 2)
    with pytest.raises(AssertionError):
        derive_grid(12)
    with pytest.raises(AssertionError):
        derive_grid(16, dp=3, tp=2, pp=2)


@pytest.mark.parametrize("arch,layer,embed", [
    ("qwen2-1.5b", 46792704, 233373696),
    ("gemma3-4b", 68157440, 671088640),
    ("qwen3-moe-30b-a3b", 623116288, 311164928),
])
def test_ml_config_param_counts(arch, layer, embed):
    """The ml-* traces are sized from these counts: pin them."""
    cfg = get_model(arch)
    assert (cfg.layer_param_count(), cfg.embed_param_count()) == \
        (layer, embed)


# ---------------------------------------------------------------------------
# Plan stacking
# ---------------------------------------------------------------------------


def test_dc_family_shares_plan_shape():
    """The whole dc-* family lowers to one plan shape by construction, so
    it stacks along the multi-trace axis."""
    plans = [P.compile_plan(tr, TINY) for tr in _dc_traces().values()]
    keys = {P.plan_shape_key(p) for p in plans}
    assert len(keys) == 1
    batch = P.stack_plans(plans, names=DC_NAMES)
    assert batch.n_traces == 4 and batch.names == DC_NAMES
    [seg] = batch.segments
    assert np.asarray(seg.xs["delta"]).shape[0] == 4   # leading T axis
    assert P.group_stackable(plans) == [[0, 1, 2, 3]]


def test_stack_rejects_shape_mismatch():
    traces = _dc_traces()
    pdc = P.compile_plan(traces["dc-poisson"], TINY)
    pml = P.compile_plan(
        SC.build_trace(SC.get_scenario("ml-qwen2-1.5b").scaled(8), TINY),
        TINY)
    assert P.plan_shape_key(pdc) != P.plan_shape_key(pml)
    with pytest.raises(AssertionError, match="different shapes"):
        P.stack_plans([pdc, pml])


# ---------------------------------------------------------------------------
# Multi-trace batched grid: equivalence + program-count bound
# ---------------------------------------------------------------------------


def test_grid_matches_serial_bit_identical_and_compiles_fewer():
    """The acceptance gate: a (4 scenarios x 6 policies — incl. the dual
    ladder and adaptive-demotion kinds) grid through the batched
    multi-trace path is bit-identical to per-trace ``simulate_trace``,
    its cold compile count scales with static groups (a small per-group
    constant — NOT with scenarios x policies), and a warm identical grid
    compiles NOTHING (every program reused across stacks and lanes)."""
    traces = _dc_traces()
    n_groups = len(group_policies(GRID))
    assert n_groups == 4
    # warm the per-policy machinery (B-lane init ops, single-trace
    # programs) so the counter below sees only the grid path's programs
    sweep_policies(traces["dc-poisson"], TINY, GRID, PM)
    want = {(tn, pn): S.simulate_trace(tr, TINY, pol, PM)[0]
            for tn, tr in traces.items() for pn, pol in GRID.items()}
    with count_compiles() as cc:
        got = sweep_scenarios(traces, TINY, GRID, PM)
    for tn in traces:
        for pn in GRID:
            assert got[tn][pn].as_dict() == want[(tn, pn)].as_dict(), \
                f"{tn}/{pn} diverged from serial replay"
    # the dc stack is ONE shape: cold programs are a per-group constant
    # (runner + init + a few eager summary ops), far under the 24-cell
    # grid; order-robust, unlike a bound that leans on prior-test warmth
    assert cc.count <= 8 * n_groups, \
        f"{cc.count} compiles > 8 x {n_groups} groups"
    with count_compiles() as cc2:
        warm = sweep_scenarios(traces, TINY, GRID, PM)
    assert cc2.count == 0, f"warm grid recompiled {cc2.count} programs"
    for tn in traces:
        for pn in GRID:
            assert warm[tn][pn].as_dict() == want[(tn, pn)].as_dict()


def test_grid_matches_step_loop_reference():
    """Multi-trace batched replay against the semantic oracle (the host
    step-loop), as tests/test_plan.py does for the single-trace path."""
    names = ["dc-poisson", "dc-onoff"]
    traces = {n: SC.build_trace(SC.get_scenario(n).scaled(8), TINY)
              for n in names}
    pols = {"ds": GRID["ds"], "pb1": GRID["pb1"]}
    got = sweep_scenarios(traces, TINY, pols, PM)
    for tn, tr in traces.items():
        for pn, pol in pols.items():
            want, _ = S.simulate_trace_reference(tr, TINY, pol, PM)
            g, w = got[tn][pn].as_dict(), want.as_dict()
            for k in w:
                np.testing.assert_allclose(g[k], w[k], rtol=1e-9,
                                           atol=1e-12,
                                           err_msg=f"{tn}/{pn}.{k}")


def test_mixed_shape_grid_covers_all_cells():
    """Scenarios that do NOT share a plan shape still sweep through
    ``sweep_scenarios`` (separate stacks), matching serial results."""
    traces = {
        "dc-poisson": SC.build_trace(
            SC.get_scenario("dc-poisson").scaled(8), TINY),
        "hpc-spectral": SC.build_trace(
            SC.get_scenario("hpc-spectral").scaled(8), TINY),
    }
    pols = {"fw": GRID["fw"], "ds": GRID["ds"]}
    got = sweep_scenarios(traces, TINY, pols, PM)
    for tn, tr in traces.items():
        for pn, pol in pols.items():
            want, _ = S.simulate_trace(tr, TINY, pol, PM)
            assert got[tn][pn].as_dict() == want.as_dict(), f"{tn}/{pn}"


# ---------------------------------------------------------------------------
# Suite runner
# ---------------------------------------------------------------------------


def test_run_suite_reports_relative_to_baseline():
    res = SC.run_suite(TINY, scenarios=["dc-poisson", "dc-onoff"],
                       policies={"ds": GRID["ds"], "pb1": GRID["pb1"]},
                       n_nodes=8)
    assert set(res) == {"dc-poisson", "dc-onoff"}
    for sc, rows in res.items():
        assert set(rows) == {"baseline", "ds", "pb1"}
        assert rows["baseline"]["exec_overhead_pct"] == 0.0
        assert rows["baseline"]["energy_saved_pct"] == 0.0
        for pn in ("ds", "pb1"):
            assert rows[pn]["makespan"] >= rows["baseline"]["makespan"]
            assert 0.0 < rows[pn]["link_energy_saved_pct"] <= 100.0
    table = SC.format_table(res)
    assert "dc-poisson" in table and "baseline" in table
    rows = list(SC.table_rows(res))
    assert len(rows) == 2 * 3
    assert {"scenario", "policy", "energy_saved_pct"} <= set(rows[0])
