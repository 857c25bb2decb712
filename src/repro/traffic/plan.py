"""Trace -> TracePlan compiler: device-resident replay plans (DESIGN.md §2).

Replaying a :class:`~repro.traffic.trace.Trace` used to be a host Python
loop: every step re-derived routes, argsorted injection times in numpy,
re-padded messages, and bounced ``ready``-clock state between host and
device.  This module compiles a (trace, topology) pair ONCE into a
:class:`TracePlan` whose arrays live on device, so the executor
(``repro.core.replay``) can run the whole trace as a few ``lax.scan`` calls
with zero per-step host work:

  * **routes**: one batched ``topo.routes_cached`` lookup for ALL messages
    of the trace (the topology-level route LRU serves whole-trace repeats
    — replanned or identically rebuilt traces, fresh equal topologies);
  * **message tables**: per-step (src, dst, bytes, links, dirs, n_hops)
    padded into a small set of shared power-of-two bucket shapes — the
    same bucketing both engines always used, now in one place;
  * **compute / barrier phases**: lowered to dense per-step arrays
    (a (n_nodes,) clock delta + a barrier flag) that become scan-step
    branches in the executor;
  * **segments**: contiguous runs of steps sharing a message bucket are
    stacked into (S, cap, ...) arrays — one compiled scan per segment
    shape.  Step counts are padded to power-of-two buckets as well, so
    compile count is bounded by distinct (cap, S-bucket) pairs, not by
    trace length.

Plans are cached per (trace, topology): every policy group of a sweep —
and every warm rerun — reuses the same device arrays instead of recomputing
routes and padding per group.  The cache keys on trace identity plus a
cheap structural fingerprint; mutating a trace after planning (appending
steps via the builder API) is detected and triggers recompilation.
"""
from __future__ import annotations

import weakref
from collections import OrderedDict
from dataclasses import dataclass, field, replace
from typing import List, Optional

import jax.numpy as jnp
import numpy as np

from repro.core.instrument import add

BUCKET_MIN = 64        # smallest message-slot bucket (shared by all engines)
STEP_BUCKET_MIN = 4    # smallest per-segment step-count bucket
MAX_STEP_PAD = 32      # cap on shared-bucket padding of a short segment
RAGGED_MIN = 8         # smallest ragged size-class cap (repack_plans)

PACKINGS = ("pow2", "ragged")


def bucket_cap(M: int, bucket_min: int = BUCKET_MIN) -> int:
    """Power-of-two capacity bucket for M messages (identical bucketing
    across the serial, batched, and plan engines keeps their recompilation
    behaviour aligned).  M <= 1 needs exactly one slot: ``max(M - 1, 0)``
    (NOT ``max(M - 1, 1)``, which silently rounded M=0/M=1 up to a 2-slot
    bucket whenever ``bucket_min`` is 1)."""
    return max(bucket_min, 1 << max(M - 1, 0).bit_length())


def step_bucket(S: int, bucket_min: int = STEP_BUCKET_MIN) -> int:
    """Power-of-two step-count bucket; same S <= 1 edge rule as
    ``bucket_cap`` (a single-step segment buckets to 1, not 2, when
    ``bucket_min`` is 1)."""
    return max(bucket_min, 1 << max(S - 1, 0).bit_length())


def ragged_cap(M: int, ragged_min: int = RAGGED_MIN) -> int:
    """Size-class capacity for M messages: the {2^k, 3*2^(k-1)} ladder
    (8, 12, 16, 24, 32, 48, 64, 96, 128, ...) used by the ragged packer.
    Twice as many classes as the power-of-two ladder bounds worst-case
    slot waste at 33% instead of 50% while keeping the number of distinct
    compiled shapes logarithmic in the largest step."""
    M = max(M, 1)
    if M <= ragged_min:
        return ragged_min
    k = (M - 1).bit_length()             # 2^(k-1) < M <= 2^k
    three_quarter = 3 << (k - 2) if k >= 2 else 1 << k
    return three_quarter if M <= three_quarter else 1 << k


def _pad_axis(a: np.ndarray, cap: int, axis: int, fill=0) -> np.ndarray:
    pad = cap - a.shape[axis]
    if pad <= 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return np.pad(a, widths, constant_values=fill)


def pad_message_table(links, dirs, nhops, t_inj, nbytes, *, axis=0,
                      bucket_min: int = BUCKET_MIN):
    """THE shared message-padding helper (serial + batched + plan engines).

    Pads every per-message array along ``axis`` to the power-of-two bucket
    of its current length and returns host numpy
    ``(links, dirs, nhops, t_inj, nbytes, valid)`` — links filled with -1,
    numerics with 0, ``valid`` marking real entries.
    """
    M = nhops.shape[axis]
    cap = bucket_cap(M, bucket_min)
    valid_shape = list(nhops.shape)
    valid_shape[axis] = cap
    valid = np.zeros(valid_shape, bool)
    np.moveaxis(valid, axis, 0)[:M] = True
    return (_pad_axis(links, cap, axis, -1), _pad_axis(dirs, cap, axis),
            _pad_axis(nhops, cap, axis),
            _pad_axis(t_inj.astype(np.float64), cap, axis),
            _pad_axis(nbytes.astype(np.float64), cap, axis), valid)


# ---------------------------------------------------------------------------
# Plan data model
# ---------------------------------------------------------------------------


@dataclass
class _HostStep:
    """One lowered replay step (host-side intermediate): an optional clock
    advance, an optional message table, and an optional barrier — applied
    in that order (DESIGN.md §3)."""
    compute: Optional[tuple] = None      # (nodes (K,), secs (K,))
    msgs: Optional[np.ndarray] = None    # (M, 3) [src, dst, bytes]
    barrier: bool = False


@dataclass
class PlanSegment:
    """A contiguous run of plan steps sharing one message bucket, stacked
    into device arrays with leading dim S (step-count, power-of-two
    padded).  ``xs`` feeds the executor's ``lax.scan`` directly."""
    cap: int                             # message slots per step (0: none)
    n_steps: int                         # real steps before S-padding
    xs: dict = field(repr=False)         # device arrays, leading dim S_pad
    host_has_msgs: np.ndarray = field(default=None, repr=False)  # (S_pad,)
    host_live: np.ndarray = field(default=None, repr=False)      # (S_pad,) i32
    host_wave: np.ndarray = field(default=None, repr=False)      # (S_pad,) i32
    host_delta: np.ndarray = field(default=None, repr=False)  # xs["delta"]
    _delta_bits: Optional[jnp.ndarray] = field(default=None, repr=False)

    @property
    def s_pad(self) -> int:
        return int(self.xs["delta"].shape[-2])

    @property
    def needs_sort(self) -> bool:
        """False when every step statically carries <=1 valid message: the
        valid slots are a prefix, so the stable injection-time argsort is
        the identity and the executor skips it (plan-time flag)."""
        return self.host_live is None \
            or int(self.host_live.max(initial=0)) > 1

    @property
    def wave_width(self) -> int:
        """Plan-time wave-schedule width (DESIGN.md §10): the largest
        canonical-order conflict-chain length over the segment's steps —
        the wave count the executor's wavefront mode runs when injection
        times tie (the common post-barrier case), and its mode heuristic's
        estimate otherwise.  Segments without the analysis report ``cap``
        (conservative: the serial trip count)."""
        if self.host_wave is None:
            return self.cap
        return int(self.host_wave.max(initial=0))

    @property
    def mean_live(self) -> float:
        """Mean live-message count over the segment's message steps — the
        prefix executor's expected dynamic trip, vs the serial scan's
        static ``cap`` (the executor cost model, DESIGN.md §10).  Stacked
        (T, S) metadata averages over every trace row."""
        if self.host_live is None:
            return float(self.cap)
        lv = self.host_live[self.host_live > 0]
        return float(lv.mean()) if lv.size else 0.0

    @property
    def mean_wave(self) -> float:
        """Mean canonical wave count over the segment's message steps —
        the chained wave executor's expected trip."""
        if self.host_wave is None:
            return float(self.cap)
        wv = self.host_wave[self.host_wave > 0]
        return float(wv.mean()) if wv.size else 0.0

    def delta_bits(self) -> jnp.ndarray:
        """The clock deltas as float64 bit patterns on the device, placed
        on first use: only a replay on exact clocks (``ieee.ExactClock``)
        reads them, and the TPU cannot take them from the placed
        float64, whose bits it does not hold."""
        if self._delta_bits is None:
            self._delta_bits = jnp.asarray(self.host_delta.view(np.uint64))
            add("placed_bytes", self.host_delta.nbytes)
        return self._delta_bits

    def nbytes(self) -> int:
        """Device bytes held by this segment's arrays."""
        return sum(int(np.dtype(x.dtype).itemsize) * int(np.prod(x.shape))
                   for x in self.xs.values())


def slot_nbytes(max_hops: int) -> int:
    """Device bytes of ONE message slot: src/dst/nhops i32 + bytes f64 +
    valid bool + per-hop links/dirs i32 pairs."""
    return 4 + 4 + 4 + 8 + 1 + 8 * max_hops


def step_fixed_nbytes(n_nodes: int) -> int:
    """Per-step device bytes independent of the message cap (clock delta +
    barrier / has_msgs flags)."""
    return 8 * n_nodes + 2


def segment_nbytes(cap: int, s_pad: int, n_nodes: int, max_hops: int) -> int:
    """Byte model of a (cap, S_pad) segment — the packer's merge-cost
    metric and the memory audit's padded-bytes column.  Matches
    ``PlanSegment.nbytes()`` for segments built by ``_stack_segment``
    (capped segments also carry a 4-byte per-step live count)."""
    per_step = step_fixed_nbytes(n_nodes) + cap * slot_nbytes(max_hops) \
        + (4 if cap else 0)
    return s_pad * per_step


@dataclass
class TracePlan:
    """A compiled, device-resident replay program for one (trace, topo)."""
    n_nodes: int
    n_links: int
    max_hops: int
    part_mask: jnp.ndarray               # (n_nodes,) bool — participants
    has_participants: bool
    busy: float                          # total compute seconds (node energy)
    n_msgs: int
    n_message_steps: int
    segments: List[PlanSegment]
    name: str = ""
    bucket_min: int = BUCKET_MIN

    @property
    def n_steps(self) -> int:
        return sum(s.n_steps for s in self.segments)

    def describe(self) -> str:
        caps = [f"{s.cap}x{s.n_steps}" for s in self.segments]
        return (f"TracePlan({self.name or 'trace'}: {self.n_msgs} msgs, "
                f"{self.n_steps} steps, segments [{', '.join(caps)}])")


# ---------------------------------------------------------------------------
# Lowering: Trace steps -> _HostSteps (phase fusion)
# ---------------------------------------------------------------------------


def _lower_steps(trace) -> List[_HostStep]:
    """Fuse the trace's phase structure into plan steps.

    A compute-only step fuses into the FOLLOWING message step (the plan
    step applies compute -> msgs -> barrier, exactly the replay order of
    the two originals); a trailing barrier-only step folds into the
    preceding plan step.  Fusion never merges two compute phases into one
    floating-point add, so clock arithmetic stays bit-identical to the
    step-loop reference engine.
    """
    out: List[_HostStep] = []
    pending: Optional[tuple] = None      # one unconsumed compute phase

    def flush():
        nonlocal pending
        if pending is not None:
            out.append(_HostStep(compute=pending))
            pending = None

    for st in trace.steps:
        has_c = st.compute_nodes is not None and len(st.compute_nodes) > 0
        has_m = st.msgs is not None and len(st.msgs) > 0
        if has_c and not has_m and not st.barrier:
            flush()
            pending = (st.compute_nodes, st.compute_secs)
            continue
        if not has_m and not st.barrier:
            continue                     # fully empty step: no-op
        if has_c:
            flush()
            comp = (st.compute_nodes, st.compute_secs)
        else:
            comp, pending = pending, None
        if not has_m and st.barrier and comp is None and out \
                and not out[-1].barrier:
            out[-1].barrier = True       # retrofit: phases then barrier
            continue
        out.append(_HostStep(compute=comp,
                             msgs=st.msgs if has_m else None,
                             barrier=st.barrier))
    flush()
    return out


# ---------------------------------------------------------------------------
# Compilation
# ---------------------------------------------------------------------------


def step_conflicts(links: np.ndarray, nhops: np.ndarray) -> np.ndarray:
    """(M, M) bool conflict matrix of one step's messages: i conflicts j
    iff their route link sets intersect (direction-agnostic — both
    directions of a link share its FSM row).  Messages sharing a link form
    a clique, so the matrix assembles per-link instead of via an O(M²H²)
    pairwise compare.  Diagonal is False."""
    M = links.shape[0]
    conf = np.zeros((M, M), bool)
    if M <= 1:
        return conf
    hop_ok = (links >= 0) & (np.arange(links.shape[1]) < nhops[:, None])
    mi, hi = np.nonzero(hop_ok)
    by_link: dict = {}
    for i, l in zip(mi.tolist(), links[mi, hi].tolist()):
        by_link.setdefault(l, []).append(i)
    for idx in by_link.values():
        if len(idx) > 1:
            conf[np.ix_(idx, idx)] = True
    np.fill_diagonal(conf, False)
    return conf


def wave_assign(conf: np.ndarray) -> np.ndarray:
    """Order-preserving greedy wave ids (1-based) for a step's messages in
    a fixed processing order: ``wave[i] = 1 + max(wave[j])`` over earlier
    conflicting ``j`` (0 if none).  Conflicting pairs land in strictly
    increasing waves matching the order, so executing wave-by-wave — each
    wave's (link-disjoint) members batched — replays the exact serial
    update sequence on every FSM row (DESIGN.md §10).  The executor runs
    the same recurrence on device against each lane's injection-time sort;
    this host twin (canonical slot order) feeds the plan-time width
    estimate and the property tests."""
    M = conf.shape[0]
    wave = np.ones(M, np.int64)
    for i in range(1, M):
        pred = conf[i, :i]
        if pred.any():
            wave[i] = wave[:i][pred].max() + 1
    return wave


def _step_wave_width(links: np.ndarray, nhops: np.ndarray) -> int:
    """Wave count of one step in canonical (slot) order — exact when
    injection times tie (stable sort = identity), the mode heuristic's
    estimate otherwise."""
    M = links.shape[0]
    if M <= 1:
        return M
    return int(wave_assign(step_conflicts(links, nhops)).max())


def _stack_segment(steps: List[_HostStep], cap: int, n_nodes: int,
                   routed: dict, H: int, S_pad: int) -> PlanSegment:
    S = len(steps)
    delta = np.zeros((S_pad, n_nodes), np.float64)
    barrier = np.zeros((S_pad,), bool)
    has_msgs = np.zeros((S_pad,), bool)
    live = np.zeros((S_pad,), np.int32)
    wave = np.zeros((S_pad,), np.int32)
    xs = {}
    if cap:
        src = np.zeros((S_pad, cap), np.int32)
        dst = np.zeros((S_pad, cap), np.int32)
        nbytes = np.zeros((S_pad, cap), np.float64)
        links = np.full((S_pad, cap, H), -1, np.int32)
        dirs = np.zeros((S_pad, cap, H), np.int32)
        nhops = np.zeros((S_pad, cap), np.int32)
        valid = np.zeros((S_pad, cap), bool)
    for i, ps in enumerate(steps):
        if ps.compute is not None:
            nodes, secs = ps.compute
            # assignment (not add.at): matches the reference engine's
            # buffered fancy-index `ready[nodes] += secs`
            delta[i][np.asarray(nodes)] = np.asarray(secs, np.float64)
        barrier[i] = ps.barrier
        if ps.msgs is not None:
            M = len(ps.msgs)
            has_msgs[i] = True
            live[i] = M
            src[i, :M] = ps.msgs[:, 0]
            dst[i, :M] = ps.msgs[:, 1]
            nbytes[i, :M] = ps.msgs[:, 2].astype(np.float64)
            l, d, nh = routed[id(ps)]
            links[i, :M] = l
            dirs[i, :M] = d
            nhops[i, :M] = nh
            valid[i, :M] = True
            wave[i] = _step_wave_width(np.asarray(l), np.asarray(nh))
    xs["delta"] = jnp.asarray(delta)
    xs["barrier"] = jnp.asarray(barrier)
    if cap:
        xs.update(
            has_msgs=jnp.asarray(has_msgs), live=jnp.asarray(live),
            src=jnp.asarray(src),
            dst=jnp.asarray(dst), nbytes=jnp.asarray(nbytes),
            links=jnp.asarray(links), dirs=jnp.asarray(dirs),
            nhops=jnp.asarray(nhops), valid=jnp.asarray(valid))
    return PlanSegment(cap=cap, n_steps=S, xs=xs, host_has_msgs=has_msgs,
                       host_live=live, host_wave=wave, host_delta=delta)


def topo_signature(topo) -> tuple:
    """``(n_nodes, n_links, max_hops)`` — the topology part of a plan's
    compiled shape (``RoutedTopology.signature`` when available)."""
    if hasattr(topo, "signature"):
        return topo.signature()
    return (topo.n_nodes, topo.n_links, topo.max_hops)


def _compile(trace, topo, bucket_min: int) -> TracePlan:
    steps = _lower_steps(trace)
    n_nodes, n_links, H = topo_signature(topo)

    # ---- one batched route lookup for the whole trace -------------------
    msg_steps = [ps for ps in steps if ps.msgs is not None]
    routed: dict = {}
    if msg_steps:
        all_src = np.concatenate([ps.msgs[:, 0] for ps in msg_steps])
        all_dst = np.concatenate([ps.msgs[:, 1] for ps in msg_steps])
        lookup = getattr(topo, "routes_cached", topo.routes)
        links, dirs, nhops = lookup(all_src, all_dst)
        off = 0
        for ps in msg_steps:
            M = len(ps.msgs)
            routed[id(ps)] = (links[off:off + M], dirs[off:off + M],
                              nhops[off:off + M])
            off += M

    # ---- segmentation: contiguous runs sharing one bucket ---------------
    runs: List[tuple] = []               # (steps, cap)
    run: List[_HostStep] = []
    run_cap: Optional[int] = None        # None until a message step joins
    for ps in steps:
        c = bucket_cap(len(ps.msgs), bucket_min) if ps.msgs is not None \
            else None
        if run and c is not None and run_cap is not None and c != run_cap:
            runs.append((run, run_cap))
            run, run_cap = [], None
        run.append(ps)
        if c is not None:
            run_cap = run_cap or c
    if run:
        runs.append((run, run_cap or 0))

    # One shared step-count bucket per cap: same-cap segments pad to the
    # longest run's bucket, so the executor compiles ONE program per
    # (static structure, cap) — no-op pad steps are a cheap cond-false,
    # extra program shapes are a ~seconds compile each.  The pad factor is
    # bounded (MAX_STEP_PAD): on fragmented traces a short segment never
    # pads past MAX_STEP_PAD x its own bucket, trading at most a couple of
    # extra program shapes for O(longest-run) pad work per fragment.
    cap_bucket = {}
    for seg_steps, cap in runs:
        cap_bucket[cap] = max(cap_bucket.get(cap, 0),
                              step_bucket(len(seg_steps)))
    segments = [
        _stack_segment(seg_steps, cap, n_nodes, routed, H,
                       min(cap_bucket[cap],
                           MAX_STEP_PAD * step_bucket(len(seg_steps))))
        for seg_steps, cap in runs]

    # ---- host-scalar bookkeeping (accumulation order matches the
    #      reference engine exactly) --------------------------------------
    busy = 0.0
    for st in trace.steps:
        if st.compute_nodes is not None and len(st.compute_nodes):
            busy += float(st.compute_secs.sum())

    part_mask = np.zeros(n_nodes, bool)
    part_mask[np.asarray(trace.nodes, np.int64)] = True

    return TracePlan(
        n_nodes=n_nodes, n_links=n_links, max_hops=H,
        part_mask=jnp.asarray(part_mask),
        has_participants=len(trace.nodes) > 0,
        busy=busy, n_msgs=int(trace.n_messages),
        n_message_steps=len(msg_steps), segments=segments,
        name=trace.name, bucket_min=bucket_min)


# ---------------------------------------------------------------------------
# Per-(trace, topo) plan cache
# ---------------------------------------------------------------------------

# id(trace) -> (weakref(trace), fingerprint, {topo: TracePlan})
_PLAN_CACHE: dict = {}
_CACHE_STATS = {"hits": 0, "misses": 0, "stack_hits": 0, "stack_misses": 0}


def _fingerprint(trace) -> tuple:
    return (len(trace.steps), trace.n_messages,
            getattr(trace, "version", 0))


def plan_nbytes(plan) -> int:
    """Resident device bytes of a :class:`TracePlan` / :class:`PlanBatch`
    (segment arrays + participant mask)."""
    n = sum(seg.nbytes() for seg in plan.segments)
    pm = plan.part_mask
    return n + int(np.dtype(pm.dtype).itemsize) * int(np.prod(pm.shape))


def compile_plan(trace, topo, bucket_min: int = BUCKET_MIN) -> TracePlan:
    """Compile (or fetch the cached) TracePlan for a (trace, topo) pair.

    The cache keys on trace identity + a structural fingerprint (step and
    message counts, builder version): every sweep group and warm rerun hits
    the same plan, while builder-API mutation after planning recompiles.
    """
    key = id(trace)
    entry = _PLAN_CACHE.get(key)
    fp = _fingerprint(trace)
    if entry is None or entry[0]() is not trace or entry[1] != fp:
        ref = weakref.ref(trace, lambda _r, k=key: _PLAN_CACHE.pop(k, None))
        entry = (ref, fp, {})
        _PLAN_CACHE[key] = entry
    plans = entry[2]
    ck = (topo, bucket_min)
    if ck not in plans:
        _CACHE_STATS["misses"] += 1
        plans[ck] = _compile(trace, topo, bucket_min)
        add("placed_bytes", plan_nbytes(plans[ck]))
    else:
        _CACHE_STATS["hits"] += 1
    return plans[ck]


def plan_cache_clear() -> None:
    _PLAN_CACHE.clear()
    _STACK_CACHE.clear()
    for k in _CACHE_STATS:
        _CACHE_STATS[k] = 0


def plan_cache_info() -> dict:
    """Cache counter surface: per-(trace, topo) plan cache hit/miss counts
    and resident device bytes, plus the same for the stack-level cache
    (``stack_plans_cached``) the sharded sweep engine rides."""
    plans = [p for e in _PLAN_CACHE.values() for p in e[2].values()]
    stacks = [b for _k, b in _STACK_CACHE.values()]
    return {"traces": len(_PLAN_CACHE), "plans": len(plans),
            "hits": _CACHE_STATS["hits"],
            "misses": _CACHE_STATS["misses"],
            "resident_bytes": sum(plan_nbytes(p) for p in plans),
            "stacks": len(_STACK_CACHE),
            "stack_hits": _CACHE_STATS["stack_hits"],
            "stack_misses": _CACHE_STATS["stack_misses"],
            "stack_resident_bytes": sum(plan_nbytes(b) for b in stacks)}


def format_cache_info(info: Optional[dict] = None) -> str:
    """One-line human-readable ``plan_cache_info`` rendering (the
    run_suite / tune_policies log line)."""
    i = info if info is not None else plan_cache_info()
    return (f"plan cache: {i['plans']} plans / {i['traces']} traces, "
            f"{i['hits']} hits / {i['misses']} misses, "
            f"{i['resident_bytes'] / 1e6:.2f} MB resident; "
            f"stacks: {i['stacks']} cached, "
            f"{i['stack_hits']} hits / {i['stack_misses']} misses, "
            f"{i['stack_resident_bytes'] / 1e6:.2f} MB resident")


# ---------------------------------------------------------------------------
# Multi-trace stacking: same-shape plans batch along a second vmapped axis
# ---------------------------------------------------------------------------


def plan_shape_key(plan: TracePlan) -> tuple:
    """Compiled-shape signature of a plan: topology shape + the per-segment
    ``(cap, S_pad)`` schedule.  Two plans with equal keys lower to identical
    executor programs, so they can stack along a leading trace axis and a
    (scenarios x policies) grid replays in ONE compiled scan per segment
    shape instead of one per (scenario, policy-group)."""
    return (plan.n_nodes, plan.n_links, plan.max_hops, plan.bucket_min,
            tuple((s.cap, int(s.xs["delta"].shape[0]))
                  for s in plan.segments))


@dataclass
class PlanBatch:
    """T same-shape TracePlans stacked along a leading trace axis.

    Mirrors :class:`TracePlan` with every device array gaining a leading
    ``T`` dim: segment ``xs`` arrays are ``(T, S_pad, ...)`` and
    ``part_mask`` is ``(T, n_nodes)``.  Host bookkeeping (``busy``,
    ``n_msgs``, participant flags) becomes per-trace numpy vectors.  The
    executor (``repro.core.replay``) vmaps its per-trace program over this
    axis, so one compiled program serves the whole (trace, policy) grid of
    a segment shape.
    """
    n_nodes: int
    n_links: int
    max_hops: int
    part_mask: jnp.ndarray               # (T, n_nodes) bool
    has_participants: np.ndarray         # (T,) bool, host
    busy: np.ndarray                     # (T,) f64, host
    n_msgs: np.ndarray                   # (T,) i64, host
    segments: List[PlanSegment]          # xs arrays lead with T
    names: List[str]
    bucket_min: int = BUCKET_MIN

    @property
    def n_traces(self) -> int:
        return len(self.names)

    def describe(self) -> str:
        caps = [f"{s.cap}x{s.n_steps}" for s in self.segments]
        return (f"PlanBatch({self.n_traces} traces "
                f"[{', '.join(self.names)}]: segments [{', '.join(caps)}])")


def stack_plans(plans: List[TracePlan], names: Optional[List[str]] = None
                ) -> PlanBatch:
    """Stack same-shape plans into one :class:`PlanBatch`.

    All plans must share ``plan_shape_key`` (same topology shape and the
    same per-segment ``(cap, S_pad)`` schedule) — use ``group_stackable``
    to partition an arbitrary plan list first.  A single plan stacks into
    a T=1 batch, so callers can route everything through the multi-trace
    executor unconditionally.
    """
    assert plans, "stack_plans needs at least one plan"
    key0 = plan_shape_key(plans[0])
    for p in plans[1:]:
        assert plan_shape_key(p) == key0, \
            f"cannot stack plans with different shapes: " \
            f"{plan_shape_key(p)} vs {key0}"
    names = list(names) if names is not None \
        else [p.name or f"trace{i}" for i, p in enumerate(plans)]
    segments = []
    for si, seg0 in enumerate(plans[0].segments):
        xs = {k: jnp.stack([p.segments[si].xs[k] for p in plans])
              for k in seg0.xs}
        host_has = np.stack([p.segments[si].host_has_msgs
                             for p in plans]) \
            if seg0.host_has_msgs is not None else None
        host_live = np.stack([p.segments[si].host_live for p in plans]) \
            if seg0.host_live is not None else None
        host_wave = np.stack([p.segments[si].host_wave for p in plans]) \
            if all(p.segments[si].host_wave is not None for p in plans) \
            else None
        segments.append(PlanSegment(
            cap=seg0.cap,
            n_steps=max(p.segments[si].n_steps for p in plans),
            xs=xs, host_has_msgs=host_has, host_live=host_live,
            host_wave=host_wave,
            host_delta=np.stack([p.segments[si].host_delta
                                 for p in plans])))
    return PlanBatch(
        n_nodes=plans[0].n_nodes, n_links=plans[0].n_links,
        max_hops=plans[0].max_hops,
        part_mask=jnp.stack([p.part_mask for p in plans]),
        has_participants=np.asarray([p.has_participants for p in plans]),
        busy=np.asarray([p.busy for p in plans], np.float64),
        n_msgs=np.asarray([p.n_msgs for p in plans], np.int64),
        segments=segments, names=names, bucket_min=plans[0].bucket_min)


def group_stackable(plans: List[TracePlan]) -> List[List[int]]:
    """Partition plan indices into stackable groups (equal
    ``plan_shape_key``), preserving first-seen order."""
    groups: dict = {}
    for i, p in enumerate(plans):
        groups.setdefault(plan_shape_key(p), []).append(i)
    return list(groups.values())


# ---------------------------------------------------------------------------
# Ragged repacking: size-class caps + tail-segment merging, stack-uniform
# ---------------------------------------------------------------------------


def _seg_host_xs(seg: PlanSegment, cap: int, H: int) -> dict:
    """One segment's arrays as host numpy, cap axis resized to ``cap``.

    Shrinking slices the (always-prefix) live slots; growing pads with
    inert slots (links -1, numerics 0, valid False).  A cap-0 segment
    materializes an all-inert message table so it can merge into a capped
    neighbour."""
    S = seg.s_pad
    out = {k: np.asarray(v) for k, v in seg.xs.items()}
    if seg.cap == 0 and cap:
        out.update(
            has_msgs=np.zeros((S,), bool),
            live=np.zeros((S,), np.int32),
            src=np.zeros((S, cap), np.int32),
            dst=np.zeros((S, cap), np.int32),
            nbytes=np.zeros((S, cap), np.float64),
            links=np.full((S, cap, H), -1, np.int32),
            dirs=np.zeros((S, cap, H), np.int32),
            nhops=np.zeros((S, cap), np.int32),
            valid=np.zeros((S, cap), bool))
        return out
    if cap < seg.cap:
        for k in ("src", "dst", "nbytes", "links", "dirs", "nhops", "valid"):
            out[k] = out[k][:, :cap]
    elif cap > seg.cap:
        for k in ("src", "dst", "nbytes", "nhops", "valid"):
            out[k] = _pad_axis(out[k], cap, 1)
        out["links"] = _pad_axis(out["links"], cap, 1, -1)
        out["dirs"] = _pad_axis(out["dirs"], cap, 1)
    return out


def _apply_schedule(plan: TracePlan, schedule: List[tuple]) -> TracePlan:
    """Materialize a repack ``schedule`` — ``[(members, cap, S_pad), ...]``
    with ``members`` = ``[(segment_index, keep_rows), ...]`` — for one
    plan.  Each member keeps its first ``keep_rows`` step rows (the
    group-wide real step count: everything beyond is shared-bucket
    padding) and members concatenate along the step axis.  Internal rows
    past a plan's OWN real steps stay as the executor's no-op padding
    (has_msgs False, zero clock delta, no barrier), so every plan of a
    stack group lands on identical array shapes."""
    H = plan.max_hops
    segments = []
    for members, cap, S_pad in schedule:
        segs = [plan.segments[si] for si, _ in members]
        hxs = [{k: v[:keep] for k, v in _seg_host_xs(s, cap, H).items()}
               for s, (_, keep) in zip(segs, members)]
        keys = ["delta", "barrier"] + (
            ["has_msgs", "live", "src", "dst", "nbytes", "links", "dirs",
             "nhops", "valid"] if cap else [])
        xs = {k: np.concatenate([h[k] for h in hxs]) for k in keys}
        S = xs["delta"].shape[0]
        for k in keys:
            xs[k] = _pad_axis(xs[k], S_pad, 0,
                              -1 if k == "links" else 0)
        host_has = _pad_axis(np.concatenate(
            [s.host_has_msgs[:keep]
             for s, (_, keep) in zip(segs, members)]), S_pad, 0)
        host_live = _pad_axis(np.concatenate(
            [s.host_live[:keep]
             for s, (_, keep) in zip(segs, members)]), S_pad, 0)
        # per-step wave widths ride along unchanged — repacking moves and
        # trims padding slots, never the live message set of a step
        host_wave = _pad_axis(np.concatenate(
            [s.host_wave[:keep] if s.host_wave is not None
             else np.zeros((keep,), np.int32)
             for s, (_, keep) in zip(segs, members)]), S_pad, 0)
        host_delta = _pad_axis(np.concatenate(
            [s.host_delta[:keep]
             for s, (_, keep) in zip(segs, members)]), S_pad, 0)
        segments.append(PlanSegment(
            cap=cap, n_steps=S,
            xs={k: jnp.asarray(v) for k, v in xs.items()},
            host_has_msgs=host_has, host_live=host_live,
            host_wave=host_wave, host_delta=host_delta))
    return replace(plan, segments=segments)


def repack_plans(plans: List[TracePlan],
                 ragged_min: int = RAGGED_MIN) -> List[TracePlan]:
    """Jointly repack same-shape plans into ragged size-class segments.

    The memory-audit remedy (DESIGN.md §9): power-of-two buckets with
    ``BUCKET_MIN`` = 64 leave 70–94% of message slots as padding across the
    catalog, and the executor's inner scan walks every padded slot.  This
    pass, applied to a WHOLE stackable group at once so the repacked plans
    still share one ``plan_shape_key`` (the contract every batching layer
    leans on):

      * **shrinks caps to size classes** — each segment's cap drops to the
        ``ragged_cap`` class of the largest LIVE step across the group
        (splitting the oversized power-of-two bucket; never grows);
      * **merges tail segments** — adjacent segments merge greedily into
        the larger cap whenever the byte model (``segment_nbytes``) says
        the merged segment is cheaper than the step-bucket padding of two
        separate ones (fragmented traces collapse to few segments, fewer
        compiled shapes);
      * re-applies the shared same-cap step-bucket rule of ``_compile``
        (bounded by ``MAX_STEP_PAD``), so compile counts stay bounded by
        distinct (cap, S-bucket) pairs exactly as before.

    Results are bit-identical to the power-of-two plans: padding slots are
    masked out of every state update and reduction (``tests/
    test_plan_memory.py`` pins ragged == pow2 == serial reference).
    Returns the input list unchanged when no segment shrinks or merges.
    """
    assert plans, "repack_plans needs at least one plan"
    key0 = plan_shape_key(plans[0])
    for p in plans[1:]:
        assert plan_shape_key(p) == key0, \
            "repack_plans operates on one stackable group at a time"
    n_nodes, H = plans[0].n_nodes, plans[0].max_hops
    segs0 = plans[0].segments

    # -- per-segment joint size class (shrink only) -----------------------
    caps = []
    for si, seg in enumerate(segs0):
        if seg.cap == 0:
            caps.append(0)
            continue
        mx = max(int(p.segments[si].host_live.max(initial=0))
                 for p in plans)
        caps.append(min(seg.cap, ragged_cap(mx, ragged_min)))

    # Group-wide real step counts: rows beyond them are shared-bucket
    # padding every plan agrees on, so the repack drops them up front and
    # re-pads once at the end (they are what makes short tail fragments
    # expensive and mergeable).
    reals = [max(p.segments[si].n_steps for p in plans)
             for si in range(len(segs0))]

    # -- greedy adjacent merging on the byte model ------------------------
    groups = [[(si, reals[si])] for si in range(len(segs0))]
    gcaps = list(caps)
    glens = list(reals)                      # concatenated real steps

    def cost(cap: int, s: int) -> int:
        return segment_nbytes(cap, step_bucket(s), n_nodes, H)

    merged = True
    while merged and len(groups) > 1:
        merged = False
        best = None
        for i in range(len(groups) - 1):
            cap_m = max(gcaps[i], gcaps[i + 1])
            save = (cost(gcaps[i], glens[i])
                    + cost(gcaps[i + 1], glens[i + 1])
                    - cost(cap_m, glens[i] + glens[i + 1]))
            if save > 0 and (best is None or save > best[0]):
                best = (save, i)
        if best is not None:
            _, i = best
            groups[i] = groups[i] + groups.pop(i + 1)
            gcaps[i] = max(gcaps[i], gcaps.pop(i + 1))
            glens[i] = glens[i] + glens.pop(i + 1)
            merged = True

    # -- shared same-cap step buckets (mirrors _compile) ------------------
    cap_bucket: dict = {}
    for cap, s in zip(gcaps, glens):
        cap_bucket[cap] = max(cap_bucket.get(cap, 0), step_bucket(s))
    spads = [min(cap_bucket[cap], MAX_STEP_PAD * step_bucket(s))
             for cap, s in zip(gcaps, glens)]

    schedule = list(zip(groups, gcaps, spads))
    if all(len(m) == 1 and cap == segs0[m[0][0]].cap
           and sp == segs0[m[0][0]].s_pad
           for m, cap, sp in schedule):
        return list(plans)               # nothing to gain — keep originals
    return [_apply_schedule(p, schedule) for p in plans]


# ---------------------------------------------------------------------------
# Stack-level cache: (plans, packing) -> PlanBatch, shared by warm sweeps
# ---------------------------------------------------------------------------

# (plan ids, names, packing) -> ((plans strong refs), PlanBatch); LRU
_STACK_CACHE: "OrderedDict[tuple, tuple]" = OrderedDict()
_STACK_CACHE_MAX = 64


def stack_plans_cached(plans: List[TracePlan],
                       names: Optional[List[str]] = None,
                       packing: str = "pow2") -> PlanBatch:
    """``stack_plans`` behind a bounded LRU, with optional ragged repacking.

    Stacking re-uploads every segment array (``jnp.stack``); the tuner's
    refinement rounds and every warm sweep used to pay that per call.  The
    cache keys on plan identity (stable through the per-(trace, topo) plan
    cache) + the packing mode, so a warm rerun reuses the stacked — and,
    under ``packing='ragged'``, repacked — device arrays outright.  The
    sharded engine (``repro.distributed.shard_sweep``) keys its per-device
    placement off these batches, giving the device-local
    (trace, topo, shard) plan-cache chain.
    """
    assert packing in PACKINGS, f"packing {packing!r} not in {PACKINGS}"
    names = list(names) if names is not None \
        else [p.name or f"trace{i}" for i, p in enumerate(plans)]
    key = (tuple(id(p) for p in plans), tuple(names), packing)
    hit = _STACK_CACHE.get(key)
    if hit is not None:
        _CACHE_STATS["stack_hits"] += 1
        _STACK_CACHE.move_to_end(key)
        return hit[1]
    _CACHE_STATS["stack_misses"] += 1
    packed = repack_plans(plans) if packing == "ragged" else plans
    batch = stack_plans(packed, names)
    _STACK_CACHE[key] = (tuple(plans), batch)
    while len(_STACK_CACHE) > _STACK_CACHE_MAX:
        _STACK_CACHE.popitem(last=False)
    return batch
