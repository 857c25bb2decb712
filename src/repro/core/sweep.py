"""Batched multi-policy sweep engine: B policies per trace replay, compiled.

The paper's whole evaluation protocol (§4) is a grid sweep — PerfBound vs
PerfBoundCorrect across degradation bounds x histogram modes x sleep states.
Replaying the trace once per policy recompiles and re-walks identical
traffic for every grid cell.  Here a :class:`~repro.core.eee.Policy` factors
into static structure (``eee.static_key``) and a numeric parameter vector
(``eee.policy_params``); policies sharing static structure are stacked along
a leading batch axis and evaluated side by side:

  * the network state (``simulator.init_net``) gains a leading policy axis
    via ``jax.vmap`` — including the PerfBound predictor state;
  * the trace is compiled ONCE per topology into a device-resident
    :class:`~repro.traffic.plan.TracePlan` (``repro.traffic.plan``) —
    routes, message padding and phase lowering are shared by EVERY group
    of the sweep through the plan cache, instead of being recomputed per
    group;
  * each plan segment runs as a single compiled ``lax.scan`` over steps
    (``repro.core.replay``) whose message phase is the vmapped
    ``simulator._message_step`` reading per-lane parameters; injection
    order is policy-dependent (latency feedback shifts per-node clocks),
    so each lane sorts its own lane's clocks with a stable ``jnp.argsort``
    INSIDE the scanned step — nothing returns to host between steps.

``sweep_policies`` is the public entry point; ``compare_policies`` in
``repro.core.simulator`` is built on top of it.  Sleep states lower to
numbers (t_w/t_s/power_frac — and the dual-mode FSM's second row plus its
``t_dst``/coalescing timers, DESIGN.md §6), so Fast Wake / Deep Sleep /
ladder variants of the same kind batch together: a typical paper grid
(2 kinds x 3 bounds x 2 states) collapses from 12 serial replays into 2
batched ones, and a whole demotion-timer or coalescing-window curve is
ONE batched replay of its kind's static group.
"""
from __future__ import annotations

from repro.core import replay
from repro.core import simulator as S
from repro.core.eee import PowerModel, static_key
from repro.core.instrument import add, span
from repro.core.replay import stack_params  # noqa: F401 (public re-export)
from repro.traffic.plan import (compile_plan, group_stackable,
                                stack_plans_cached)


# ---------------------------------------------------------------------------
# Grouping
# ---------------------------------------------------------------------------


def group_policies(policies: dict) -> list:
    """Partition {name: Policy} into static-structure groups.

    Returns a list of name-lists; each group compiles to ONE batched program
    (order inside a group and across groups follows insertion order).
    """
    groups: dict = {}
    for name, pol in policies.items():
        groups.setdefault(static_key(pol), []).append(name)
    return list(groups.values())


# ---------------------------------------------------------------------------
# Batched trace replay (plan executor wrapper)
# ---------------------------------------------------------------------------


def _sweep_group(trace, topo, names, pols, pm):
    """Replay ``trace`` once for a static-structure group of B policies."""
    plan = compile_plan(trace, topo)
    nets, t_end, lat_sum, lat_max, _ = replay.replay_plan(plan, pols, pm)
    lanes = S.lane_sums(nets, t_end, pols, topo.n_links)
    out = {}
    for b, name in enumerate(names):
        out[name] = S.summarize(lanes[b], float(t_end[b]), plan.busy,
                                float(lat_sum[b]), float(lat_max[b]),
                                plan.n_msgs, pols[b], pm, topo)
    return out


def sweep_policies(trace, topo, policies: dict, pm: PowerModel | None = None,
                   max_group: int | None = None) -> dict:
    """Evaluate every policy in {name: Policy} over one trace, batched.

    Policies are grouped by static structure (``eee.static_key``); each
    group replays the trace ONCE with a leading policy axis of width B and
    a single compiled scan per plan segment.  All groups share one cached
    TracePlan, so routes and padding are computed once per (trace, topo) —
    not once per group.  Returns {name: SimResult} in the caller's
    insertion order — results match serial ``simulator.simulate_trace``
    (and the step-loop reference engine) per policy to float64 tolerance.

    ``max_group`` caps the batch width (splits big groups), bounding device
    memory at paper scale: predictor state is O(B * n_links * hist_bins).
    """
    pm = pm or PowerModel()
    out = {}
    for names in group_policies(policies):
        cap = max_group or len(names)
        for i in range(0, len(names), cap):
            chunk = names[i:i + cap]
            out.update(_sweep_group(trace, topo, chunk,
                                    [policies[n] for n in chunk], pm))
    return {name: out[name] for name in policies}


# ---------------------------------------------------------------------------
# (scenarios x policies) grid: multi-trace batched replay
# ---------------------------------------------------------------------------


def sweep_cells(traces: dict, topo, cells: dict,
                pm: PowerModel | None = None,
                max_group: int | None = None,
                packing: str = "pow2") -> dict:
    """Evaluate a RAGGED (trace x policy) grid, batched along both axes.

    ``cells`` maps each trace name to its own {policy_name: Policy} dict —
    the general case of :func:`sweep_scenarios`, where different traces may
    request different policy subsets (the auto-tuner's refinement rounds
    keep only the surviving (scenario, static-group) cells).  Policies
    sharing a name across traces must be equal — a name is one grid column.

    Batching stays maximal despite the raggedness: traces stack by compiled
    plan shape exactly as in ``sweep_scenarios``, and within a stack each
    static policy group replays the UNION of the stack's requested lanes in
    one vmapped program per segment shape (the B policy axis is shared by
    every trace lane of a program, so evaluating a superset costs vmap
    lanes, not programs).  Only the requested cells are summarized and
    returned: ``{trace_name: {policy_name: SimResult}}`` in the callers'
    insertion orders, every cell bit-identical to that trace's own serial
    ``simulator.simulate_trace``.

    ``max_group`` caps the policy-batch width exactly as in
    ``sweep_policies``; device memory scales with T x B lanes.

    ``packing`` selects the stacked plans' segment layout: ``"pow2"``
    (the production default) or ``"ragged"`` (size-class caps + merged
    tails via ``plan.repack_plans`` — less padding memory and inner-scan
    work, bit-identical results).  Stacked batches come from the
    ``stack_plans_cached`` LRU either way, so warm sweeps reuse resident
    device arrays.

    When a device mesh is active (``repro.distributed.shard_sweep`` —
    ``use_mesh``/``set_mesh``, or auto mode with >1 visible device), each
    (T, B) replay dispatches onto the mesh with the plan arrays sharded
    along the trace axis; results stay bit-identical.
    """
    from repro.distributed import shard_sweep
    pm = pm or PowerModel()
    tnames = list(cells)
    for tn in tnames:
        for pn, pol in cells[tn].items():
            first = next(c[pn] for c in cells.values() if pn in c)
            assert pol == first, \
                f"policy {pn!r} differs across traces (one name, one column)"
    with span("repro.plan.lower"):
        plans = [compile_plan(traces[n], topo) for n in tnames]
    out: dict = {n: {} for n in tnames}
    for idx in group_stackable(plans):
        with span("repro.plan.stack"):
            batch = stack_plans_cached([plans[i] for i in idx],
                                       [tnames[i] for i in idx],
                                       packing=packing)
        union: dict = {}
        for gi in idx:
            union.update(cells[tnames[gi]])
        for pnames in group_policies(union):
            cap = max_group or len(pnames)
            for i in range(0, len(pnames), cap):
                chunk = pnames[i:i + cap]
                pols = [union[n] for n in chunk]
                mesh = shard_sweep.active_mesh(batch.n_traces, len(chunk))
                if mesh is not None:
                    nets, t_end, lat_sum, lat_max = \
                        shard_sweep.replay_plans_sharded(
                            batch, pols, pm, mesh)
                else:
                    nets, t_end, lat_sum, lat_max = replay.replay_plans(
                        batch, pols, pm)
                # the whole (T, B) grid reduced on the device, one small
                # readback; the net state itself never leaves the device
                with span("repro.sweep.readback"):
                    lanes = S.lane_sums(nets, t_end, pols, topo.n_links)
                add("readback_bytes", lanes.nbytes)
                with span("repro.sweep.summarize"):
                    for ti, gi in enumerate(idx):
                        want = cells[tnames[gi]]
                        for b, pname in enumerate(chunk):
                            if pname not in want:
                                continue
                            out[tnames[gi]][pname] = S.summarize(
                                lanes[ti, b], float(t_end[ti, b]),
                                float(batch.busy[ti]),
                                float(lat_sum[ti, b]),
                                float(lat_max[ti, b]),
                                int(batch.n_msgs[ti]), pols[b], pm, topo)
    return {tn: {pn: out[tn][pn] for pn in cells[tn]} for tn in cells}


def sweep_scenarios(traces: dict, topo, policies: dict,
                    pm: PowerModel | None = None,
                    max_group: int | None = None,
                    packing: str = "pow2") -> dict:
    """Evaluate a full (traces x policies) grid, batched along BOTH axes.

    ``traces`` is {name: Trace}.  Each trace compiles (or fetches) its
    cached :class:`~repro.traffic.plan.TracePlan`; plans sharing a compiled
    shape (``plan.plan_shape_key``) stack along a leading trace axis
    (``plan.stack_plans``), and each static policy group replays the whole
    stack in one vmapped program per segment shape
    (``replay.replay_plans``).  Compile count is therefore bounded by
    distinct (segment shape, T, B) triples — not by traces x policy-groups:
    stack groups with equal segment shapes share programs, and singleton
    stacks (T=1) still reuse any equal-shape program.

    Returns ``{trace_name: {policy_name: SimResult}}`` in the callers'
    insertion orders; every cell is bit-identical to that trace's own
    serial ``simulator.simulate_trace`` under the same policy.  The
    rectangular case of :func:`sweep_cells`.

    ``max_group`` caps the policy-batch width exactly as in
    ``sweep_policies``; device memory scales with T x B lanes.
    """
    return sweep_cells(traces, topo, {tn: policies for tn in traces},
                       pm, max_group=max_group, packing=packing)
