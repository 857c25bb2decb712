"""Sharded multi-device sweep engine: bit-identity with the vmapped
single-device engine and the serial oracle, mesh selection, placement
caching, and warm-rerun compile counts.

Multi-device cases run under forced host devices (CI sets
``XLA_FLAGS=--xla_force_host_platform_device_count=8``) and skip
gracefully on a single-device host.
"""
import jax
import numpy as np
import pytest

from repro.core import replay
from repro.core import simulator as S
from repro.core import sweep as W
from repro.core.eee import Policy, PowerModel
from repro.core.instrument import count_compiles, span_log
from repro.distributed import shard_sweep as SS
from repro.scenarios.spec import build_trace
from repro.scenarios.suite import resolve
from repro.topology.fattree import small_fattree
from repro.topology.megafly import small_topology
from repro.traffic.plan import compile_plan, stack_plans

PM = PowerModel()
TINY = small_topology(n_groups=3, leaves=2, spines=2, nodes_per_leaf=2)

multi_device = pytest.mark.skipif(
    jax.device_count() < 2,
    reason="needs >1 device (XLA_FLAGS=--xla_force_host_platform_"
           "device_count=8)")

# >= 3 policy kinds (plus the always-on baseline riding via sweep paths)
GRID = {
    "none": Policy(kind="none"),
    "fixed-ds": Policy(kind="fixed", t_pdt=1e-4, sleep_state="deep_sleep"),
    "pb-1pct": Policy(kind="perfbound", bound=0.01,
                      sleep_state="deep_sleep"),
    "dual": Policy(kind="dual", t_pdt=1e-5, t_dst=2e-4,
                   sleep_state="fast_wake", deep_state="deep_sleep"),
    "predict": Policy(kind="predict", t_pdt=1e-5, t_dst=2e-4,
                      forecast_weight=0.5, forecast_margin=2.0,
                      sleep_state="fast_wake", deep_state="deep_sleep"),
}


def _dc_traces(topo):
    specs = resolve(["dc-poisson", "dc-hotspot", "dc-onoff"], n_nodes=8)
    return {n: build_trace(s, topo) for n, s in specs.items()}


def _assert_tree_equal(a, b):
    fa = jax.tree_util.tree_leaves(jax.tree.map(np.asarray, a))
    fb = jax.tree_util.tree_leaves(jax.tree.map(np.asarray, b))
    assert len(fa) == len(fb)
    for x, y in zip(fa, fb):
        assert np.array_equal(x, y, equal_nan=True)


# ---------------------------------------------------------------------------
# Mesh selection
# ---------------------------------------------------------------------------


@multi_device
def test_mesh_for_minimizes_padding():
    n = jax.device_count()
    m = SS.mesh_for(n, 1000)             # T == device count, B huge
    assert m.shape["trace"] * m.shape["lane"] == n
    assert m.shape["trace"] == n         # perfect T split, no padding
    m = SS.mesh_for(1, 8 * n)
    assert m.shape["lane"] == n          # T=1 -> all lanes
    m = SS.mesh_for(3, 5)
    assert m.shape["trace"] * m.shape["lane"] == n


def test_active_mesh_gating():
    assert SS.active_mesh(4, 16) is None          # nothing installed
    with SS.use_mesh():                           # auto mode
        if jax.device_count() > 1:
            assert SS.active_mesh(4, 16) is not None
            # grid smaller than the device pool: stay single-device
            assert SS.active_mesh(1, 1) is None
        else:
            assert SS.active_mesh(4, 16) is None
    assert SS.active_mesh(4, 16) is None          # scope restored


# ---------------------------------------------------------------------------
# Bit-identity: sharded == vmapped == serial
# ---------------------------------------------------------------------------


@multi_device
@pytest.mark.parametrize("topo", [TINY, small_fattree()],
                         ids=["megafly", "fattree"])
def test_replay_plans_sharded_bit_identical(topo):
    """Uneven T (3) and B (5) force both pad paths; every output —
    including the opaque net-state pytree — must be bit-identical to the
    single-device multi-trace engine."""
    traces = _dc_traces(topo)
    plans = [compile_plan(t, topo) for t in traces.values()]
    batch = stack_plans(plans, list(traces))
    pols = [Policy(kind="fixed", t_pdt=float(t), sleep_state="deep_sleep")
            for t in np.geomspace(1e-7, 1e-2, 5)]
    ref = replay.replay_plans(batch, pols, PM)
    got = SS.replay_plans_sharded(batch, pols, PM,
                                  SS.mesh_for(batch.n_traces, len(pols)))
    for k, a, b in zip(("t_end", "lat_sum", "lat_max"), ref[1:], got[1:]):
        assert np.array_equal(a, b), k
    _assert_tree_equal(ref[0], got[0])


@multi_device
def test_sharded_replay_records_the_replay_layers():
    """The sharded branch opens the same dispatch / wait spans as the
    single-device engine and counts the padded (T, B) accumulators it
    brings to host, and the one placement of the group's parameters."""
    import math
    traces = _dc_traces(TINY)
    batch = stack_plans([compile_plan(t, TINY) for t in traces.values()],
                        list(traces))
    pols = [Policy(kind="fixed", t_pdt=float(t)) for t in (1e-6, 1e-5)]
    mesh = SS.mesh_for(batch.n_traces, len(pols))
    replay._PARAMS_CACHE.clear()
    with span_log() as log:
        SS.replay_plans_sharded(batch, pols, PM, mesh)
    assert [s.name for s in log.spans] == ["repro.replay.dispatch",
                                           "repro.replay.wait"]
    assert all(s.parent is None for s in log.spans)
    dt, db = mesh.shape["trace"], mesh.shape["lane"]
    cells = (math.ceil(batch.n_traces / dt) * dt
             * math.ceil(len(pols) / db) * db)
    assert log.counts == {"readback_bytes": 3 * 8 * cells,
                          "params_placed": 1}


@multi_device
@pytest.mark.parametrize("topo", [TINY, small_fattree()],
                         ids=["megafly", "fattree"])
def test_sweep_cells_sharded_matches_serial(topo):
    """The wired path: ``sweep_cells`` under an active mesh == the
    single-device sweep == serial ``simulate_trace``, across >= 3 policy
    kinds and both topologies."""
    traces = _dc_traces(topo)
    cells = {tn: GRID for tn in traces}
    want = W.sweep_cells(traces, topo, cells, PM)
    with SS.use_mesh():
        got = W.sweep_cells(traces, topo, cells, PM)
    for tn in traces:
        for pn in GRID:
            assert got[tn][pn].as_dict() == want[tn][pn].as_dict(), \
                (tn, pn)
    # spot-check one trace against the serial oracle per policy kind
    tn = next(iter(traces))
    for pn, pol in GRID.items():
        serial, _ = S.simulate_trace(traces[tn], topo, pol, PM)
        assert got[tn][pn].as_dict() == serial.as_dict(), (tn, pn)


@multi_device
def test_sharded_ragged_matches_pow2():
    """Ragged packing + mesh simultaneously: still bit-identical."""
    traces = _dc_traces(TINY)
    cells = {tn: GRID for tn in traces}
    want = W.sweep_cells(traces, TINY, cells, PM)
    with SS.use_mesh():
        got = W.sweep_cells(traces, TINY, cells, PM, packing="ragged")
    for tn in traces:
        for pn in GRID:
            assert got[tn][pn].as_dict() == want[tn][pn].as_dict(), \
                (tn, pn)


# ---------------------------------------------------------------------------
# Warm reruns: zero compiles, cached placement
# ---------------------------------------------------------------------------


@multi_device
def test_warm_rerun_compiles_nothing():
    traces = _dc_traces(TINY)
    plans = [compile_plan(t, TINY) for t in traces.values()]
    batch = stack_plans(plans, list(traces))
    pols = [Policy(kind="fixed", t_pdt=float(t), sleep_state="deep_sleep")
            for t in np.geomspace(1e-6, 1e-3, 4)]
    mesh = SS.mesh_for(batch.n_traces, len(pols))
    cold = SS.replay_plans_sharded(batch, pols, PM, mesh)
    before = SS.placement_cache_info()
    with count_compiles() as cc:
        warm = SS.replay_plans_sharded(batch, pols, PM, mesh)
    assert cc.count == 0
    after = SS.placement_cache_info()
    assert after["hits"] == before["hits"] + 1
    assert after["misses"] == before["misses"]
    for a, b in zip(cold[1:], warm[1:]):
        assert np.array_equal(a, b)
