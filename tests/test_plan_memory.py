"""Plan memory audit + ragged repacking: bucket edge cases, byte
accounting, and bit-identity of repacked plans vs the pow2 layout and the
serial oracle."""
import numpy as np
import pytest

from repro.analysis import plan_memory as PMEM
from repro.core import replay
from repro.core import simulator as S
from repro.core.eee import Policy, PowerModel
from repro.scenarios.spec import build_trace
from repro.scenarios.suite import resolve
from repro.topology.megafly import small_topology
from repro.traffic.generators import small_apps
from repro.traffic.plan import (
    bucket_cap, compile_plan, group_stackable, plan_cache_clear,
    plan_cache_info, plan_nbytes, plan_shape_key, ragged_cap, repack_plans,
    stack_plans, stack_plans_cached, step_bucket)
from repro.traffic.trace import Trace

PM = PowerModel()
TINY = small_topology(n_groups=3, leaves=2, spines=2, nodes_per_leaf=2)

POLS = [Policy(kind="fixed", t_pdt=1e-5, sleep_state="deep_sleep"),
        Policy(kind="perfbound", bound=0.01, sleep_state="deep_sleep"),
        Policy(kind="dual", t_pdt=1e-5, t_dst=2e-4,
               sleep_state="fast_wake", deep_state="deep_sleep")]


# ---------------------------------------------------------------------------
# Bucket edge cases (satellite: M=0 / S=1 regressions)
# ---------------------------------------------------------------------------


def test_bucket_cap_zero_one_edges():
    # with bucket_min=1, M<=1 needs exactly ONE slot (the historical
    # max(M-1, 1) rounded both up to a 2-slot bucket)
    assert bucket_cap(0, bucket_min=1) == 1
    assert bucket_cap(1, bucket_min=1) == 1
    assert bucket_cap(2, bucket_min=1) == 2
    assert bucket_cap(3, bucket_min=1) == 4
    # the production floor still dominates small M
    assert bucket_cap(0) == 64
    assert bucket_cap(64) == 64
    assert bucket_cap(65) == 128


def test_step_bucket_zero_one_edges():
    assert step_bucket(0, bucket_min=1) == 1
    assert step_bucket(1, bucket_min=1) == 1
    assert step_bucket(2, bucket_min=1) == 2
    assert step_bucket(5, bucket_min=1) == 8
    # production floor
    assert step_bucket(1) == 4
    assert step_bucket(4) == 4
    assert step_bucket(5) == 8


def test_ragged_cap_ladder():
    # the {2^k, 3*2^(k-1)} ladder: 8, 12, 16, 24, 32, 48, 64, 96, 128
    assert ragged_cap(0) == 8 and ragged_cap(1) == 8 and ragged_cap(8) == 8
    assert ragged_cap(9) == 12 and ragged_cap(12) == 12
    assert ragged_cap(13) == 16 and ragged_cap(16) == 16
    assert ragged_cap(17) == 24 and ragged_cap(24) == 24
    assert ragged_cap(25) == 32
    assert ragged_cap(48) == 48 and ragged_cap(49) == 64
    assert ragged_cap(96) == 96 and ragged_cap(97) == 128
    # never exceeds the pow2 bucket, never undershoots M
    for M in range(1, 300):
        c = ragged_cap(M)
        assert M <= c <= bucket_cap(M, bucket_min=8)


# ---------------------------------------------------------------------------
# Ragged repacking: equivalence + byte reduction
# ---------------------------------------------------------------------------


def _dc_plans():
    specs = resolve(["dc-poisson", "dc-hotspot", "dc-onoff", "dc-incast"],
                    n_nodes=8)
    traces = {n: build_trace(s, TINY) for n, s in specs.items()}
    return list(traces), [compile_plan(t, TINY) for t in traces.values()]


def test_repack_keeps_one_shape_key_and_shrinks():
    names, plans = _dc_plans()
    rp = repack_plans(plans)
    assert len({plan_shape_key(p) for p in rp}) == 1
    assert sum(plan_nbytes(p) for p in rp) < sum(plan_nbytes(p)
                                                 for p in plans)
    # still stackable as ONE group
    assert len(group_stackable(rp)) == 1


def test_repack_bit_identical_to_pow2_and_serial():
    names, plans = _dc_plans()
    b0 = stack_plans(plans, names)
    b1 = stack_plans(repack_plans(plans), names)
    r0 = replay.replay_plans(b0, POLS, PM)
    r1 = replay.replay_plans(b1, POLS, PM)
    for k, a, b in zip(("t_end", "lat_sum", "lat_max"), r0[1:], r1[1:]):
        assert np.array_equal(a, b), k
    # and vs the serial oracle, summarized field by field
    specs = resolve(["dc-poisson"], n_nodes=8)
    tr = build_trace(specs["dc-poisson"], TINY)
    for pol in POLS:
        want, _ = S.simulate_trace(tr, TINY, pol, PM)
        plan = repack_plans([compile_plan(tr, TINY)])[0]
        nets, t_end, ls, lm, _ = replay.replay_plan(plan, [pol], PM)
        got = S.summarize(S.lane_sums(nets, t_end, [pol], TINY.n_links)[0],
                          float(t_end[0]), plan.busy, float(ls[0]),
                          float(lm[0]), plan.n_msgs, pol, PM, TINY)
        assert got.as_dict() == want.as_dict()


def _fragmented_trace():
    """Alternating 60/70-message single steps: six 1-step pow2 segments
    (caps 64/128) that the ragged packer should merge."""
    nodes = np.arange(8, dtype=np.int64)
    tr = Trace(nodes=nodes, name="frag")
    rng = np.random.default_rng(0)
    for r in range(3):
        tr.compute(rng.uniform(1e-5, 1e-4, 8))
        tr.messages([[int(i % 8), int((i + 1) % 8), 4096]
                     for i in range(60)], barrier=False)
        tr.messages([[int(i % 8), int((i + 3) % 8), 2048]
                     for i in range(70)], barrier=(r == 2))
    return tr


def test_repack_merges_tail_fragments():
    pl = compile_plan(_fragmented_trace(), TINY)
    assert len(pl.segments) == 6
    rp = repack_plans([pl])[0]
    assert len(rp.segments) < len(pl.segments)
    assert plan_nbytes(rp) < plan_nbytes(pl)
    r0 = replay.replay_plans(stack_plans([pl]), POLS, PM)
    r1 = replay.replay_plans(stack_plans([rp]), POLS, PM)
    for k, a, b in zip(("t_end", "lat_sum", "lat_max"), r0[1:], r1[1:]):
        assert np.array_equal(a, b), k


def test_repack_identity_when_nothing_to_gain():
    # a segment already at its ragged cap and real step bucket
    nodes = np.arange(8, dtype=np.int64)
    tr = Trace(nodes=nodes, name="full")
    for _ in range(4):
        tr.messages([[int(i % 8), int((i + 1) % 8), 1024]
                     for i in range(64)], barrier=False)
    pl = compile_plan(tr, TINY)
    assert [s.cap for s in pl.segments] == [64]
    rp = repack_plans([pl])
    assert rp[0] is pl                   # returned unchanged, not rebuilt


def test_repack_reduces_worst_catalog_scenario():
    """The acceptance criterion: ragged packing reduces padded bytes on
    the worst-waste catalog scenario (app-lammps at 80 nodes)."""
    topo = small_topology()
    tr = small_apps(topo)["lammps"]
    pl = compile_plan(tr, topo)
    rp = repack_plans([pl])[0]
    assert plan_nbytes(rp) < 0.6 * plan_nbytes(pl)
    pol = POLS[0]
    r0 = replay.replay_plans(stack_plans([pl]), [pol], PM)
    r1 = replay.replay_plans(stack_plans([rp]), [pol], PM)
    assert np.array_equal(r0[2], r1[2])
    assert np.array_equal(r0[3], r1[3])


# ---------------------------------------------------------------------------
# Stack-level cache + counter surface
# ---------------------------------------------------------------------------


def test_stack_cache_counters_and_reuse():
    plan_cache_clear()
    names, plans = _dc_plans()
    b1 = stack_plans_cached(plans, names, packing="ragged")
    b2 = stack_plans_cached(plans, names, packing="ragged")
    assert b1 is b2
    b3 = stack_plans_cached(plans, names, packing="pow2")
    assert b3 is not b1
    info = plan_cache_info()
    assert info["stack_hits"] == 1 and info["stack_misses"] == 2
    assert info["stacks"] == 2
    assert info["stack_resident_bytes"] > 0
    assert info["plans"] == 4 and info["misses"] >= 4
    assert info["resident_bytes"] > 0
    plan_cache_clear()
    info = plan_cache_info()
    assert info["stacks"] == 0 and info["stack_hits"] == 0


# ---------------------------------------------------------------------------
# The audit itself
# ---------------------------------------------------------------------------


def test_audit_plan_accounting():
    names, plans = _dc_plans()
    for name, plan in zip(names, plans):
        a = PMEM.audit_plan(plan, name)
        assert a.live_bytes <= a.padded_bytes
        assert 0.0 <= a.waste < 1.0
        # dc traces are BUCKET_MIN-dominated: most slots are padding
        assert a.waste > 0.5


def test_audit_catalog_tiny():
    a = PMEM.audit_catalog(TINY, scenarios=["dc-poisson", "dc-hotspot",
                                            "dc-onoff", "dc-incast"],
                           n_nodes=8)
    assert len(a.plans) == 4
    assert a.ragged_bytes < a.pow2_bytes
    assert 0.0 < a.ragged_saving < 1.0
    assert a.worst(2)[0].waste >= a.worst(2)[1].waste
    out = PMEM.table({TINY.n_nodes: a})
    assert "ragged_saving" in out and str(TINY.n_nodes) in out
