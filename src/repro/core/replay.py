"""Compiled trace replay: execute a TracePlan as ``lax.scan`` over steps.

Stage 2 of the plan/execute split (DESIGN.md §2).  The executor carries
(``net`` EEE/predictor state, per-node ``ready`` clocks, latency
accumulators) entirely on device across the whole trace:

  * injection-time ordering runs as a **stable ``jnp.argsort`` inside the
    scanned step** (per batch lane — each policy's latency feedback gives
    it a different replay order), replacing the per-step host sorts;
  * delivery maxima update ``ready`` via **scatter-max** (invalid slots
    carry -inf, so padding never races);
  * compute advances and barriers are **scan-step branches**: a dense
    per-step clock delta plus a masked participant-max select;
  * message-less steps skip the message machinery through a ``lax.cond``
    on the plan's per-step ``has_msgs`` flag.

The serial engine is the B=1 case of the batched one: ``policies`` lanes
share a canonical static proto (``eee.canonical_proto``) and read their
numerics lane-wise from a stacked parameter vector, so one compiled
program serves every policy of a static group — and every B — per segment
shape.  The ``net`` carry is an opaque pytree to this layer: the FSM
fields the dual-mode kinds add (``deadline2``/``time_sleep2``/``n_deep``,
plus the coalescing-cycle state of the ``coalesce`` kind — DESIGN.md §6)
vmap over the B policy axis and the T trace axis like every other entry,
with no executor changes.  Between segments only jitted-call dispatch happens on host; the
carry never leaves the device (``tests/test_plan.py`` pins this with a
``jax.transfer_guard``).
"""
from __future__ import annotations

from contextlib import contextmanager
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import simulator as S
from repro.core.eee import (PARAM_FIELDS, Policy, PowerModel,
                            canonical_proto, policy_params)


def stack_params(pols) -> dict:
    """Stack each policy's numeric parameter vector into (B,) f64 arrays."""
    cols = [policy_params(p) for p in pols]
    return {f: jnp.asarray([c[f] for c in cols], jnp.float64)
            for f in PARAM_FIELDS}


# ---------------------------------------------------------------------------
# Wavefront mode selection (DESIGN.md §10)
# ---------------------------------------------------------------------------

#: ``off`` pins the classic length-``cap`` serial scan; ``on`` runs the
#: plan-scheduled message phase (best of prefix / chained waves per
#: segment); ``auto`` additionally keeps the scan when the cost model
#: predicts no win.  ``prefix`` / ``chain`` force one lowering (tests).
WAVEFRONT_MODES = ("auto", "on", "off", "prefix", "chain")
_WAVEFRONT = "auto"

#: Per-segment executor cost model (CPU XLA, microseconds per message
#: step at cap 64 / B 1 — DESIGN.md §10).  The serial scan walks every
#: padded slot; the prefix loop runs only the step's LIVE slots (plus the
#: per-step sort/dispatch fixed cost); a chained wave's marginal cost is
#: two chain gathers + dense slot math, with the chain setup (argsorts,
#: row gathers, final scatters) as a fixed per-step term.
SCAN_SLOT_US = 9.0
PREFIX_FIXED_US = 125.0
PREFIX_SLOT_US = 8.0
CHAIN_FIXED_US = 340.0
WAVE_US = 23.0


def set_wavefront(mode: str) -> None:
    """Select the message-phase executor mode.  All modes produce
    bit-identical results; only wall-clock differs."""
    global _WAVEFRONT
    assert mode in WAVEFRONT_MODES, \
        f"wavefront mode {mode!r} not in {WAVEFRONT_MODES}"
    _WAVEFRONT = mode


@contextmanager
def wavefront_mode(mode: str):
    """Scoped :func:`set_wavefront`."""
    prev = _WAVEFRONT
    set_wavefront(mode)
    try:
        yield
    finally:
        set_wavefront(prev)


def phase_costs(seg, proto: Policy) -> dict:
    """Predicted per-message-step cost (µs) of each executor lowering for
    one segment, from the plan's host metadata: mean live count (prefix
    trip), mean canonical wave count (chain trip), and the static cap
    (scan trip).  ``chain`` is absent for protos outside
    :func:`S.chain_spec` — their fallback wave loop re-scatters every cap
    slot per wave and never wins."""
    costs = {"scan": SCAN_SLOT_US * seg.cap,
             "prefix": PREFIX_FIXED_US + PREFIX_SLOT_US * seg.mean_live}
    if S.chain_spec(proto) is not None:
        costs["chain"] = CHAIN_FIXED_US + WAVE_US * seg.mean_wave
    return costs


def _phase_mode(seg, proto: Policy, collect_events: bool = False) -> str:
    """Executor lowering for one segment: ``scan``, ``prefix`` or
    ``chain``.  Event collection always runs the serial scan (events
    stack per-message in replay order)."""
    if seg.cap == 0 or collect_events or _WAVEFRONT == "off":
        return "scan"
    if _WAVEFRONT in ("prefix", "chain"):
        return _WAVEFRONT
    costs = phase_costs(seg, proto)
    if _WAVEFRONT == "on":
        del costs["scan"]            # forced: never the serial scan
    return min(costs, key=costs.get)


def _seg_flags(seg, proto: Policy, collect_events: bool = False) -> tuple:
    """(mode, needs_sort) runner flags, canonicalized so message-less
    segments share one program key."""
    if not seg.cap:
        return "scan", True
    return _phase_mode(seg, proto, collect_events), seg.needs_sort


# ---------------------------------------------------------------------------
# Compiled per-segment runner
# ---------------------------------------------------------------------------


def _row_chain(rows):
    """Per-slot predecessor chain of one step's flat slot->row mapping.

    ``pred[k]`` is the latest earlier slot writing the same row (self when
    none); ``last[k]`` marks the row's final writer.  ONE stable argsort
    groups each row's slots in slot order, so following ``pred`` replays a
    row's writers in exactly the serial execution order — this is the whole
    conflict structure the chained wavefront executor needs, at O(K log K)
    instead of the O(K^2) pairwise conflict matrix."""
    K = rows.shape[0]
    # int32 slot indices (x64 would make them int64): the same values,
    # and the TPU compiles the chained wave loop in half the time
    ordi = jnp.argsort(rows, stable=True).astype(jnp.int32)
    inv = jnp.argsort(ordi).astype(jnp.int32)
    r_s = rows[ordi]
    same_prev = jnp.concatenate(
        [jnp.zeros((1,), bool), r_s[1:] == r_s[:-1]])
    prev_slot = jnp.concatenate([ordi[:1], ordi[:-1]])
    pred_s = jnp.where(same_prev, prev_slot, ordi)
    last_s = jnp.concatenate([r_s[1:] != r_s[:-1], jnp.ones((1,), bool)])
    return pred_s[inv], last_s[inv]


def _conflicts(links, nhops, valid):
    """(cap, cap) bool conflict matrix of one step's messages, on device.

    Messages conflict iff their active hop link sets intersect (they touch
    a shared per-link FSM row).  Computed in UNSORTED slot space from the
    plan's static route arrays — lane-invariant, so the step computes it
    once outside the B vmap and each lane permutes it into its own
    injection order with ``conf[order][:, order]``."""
    cap, H = links.shape
    hop_ok = (links >= 0) & (jnp.arange(H) < nhops[:, None]) & valid[:, None]
    eq = links[:, None, :, None] == links[None, :, None, :]
    ok = hop_ok[:, None, :, None] & hop_ok[None, :, None, :]
    conf = (eq & ok).reshape(cap, cap, H * H).any(-1)
    return conf & ~jnp.eye(cap, dtype=bool)


def _make_run(proto: Policy, pm: PowerModel, n_links: int, cap: int,
              collect_events: bool, mode: str = "scan",
              needs_sort: bool = True):
    """Build the (un-jitted) per-trace segment program: one ``lax.scan``
    over a segment's steps with B policy lanes vmapped inside the step.

    ``_segment_runner`` jits it directly (the single-trace path);
    ``_multi_segment_runner`` vmaps it once more over a leading trace axis
    (the ``PlanBatch`` path) — same step arithmetic, so per-lane results
    are bit-identical between the two.

    ``mode`` selects the message-phase lowering (DESIGN.md §10), all
    bit-identical to the ``scan`` baseline:

    * ``"scan"`` — the classic length-``cap`` serial inner scan;
    * ``"prefix"`` — a dynamic loop over the step's VALID slot prefix
      (trip = the plan's per-step live count, not the padded cap);
    * ``"chain"`` — conflict-free waves over the per-row predecessor
      chain, each wave one batch of dense slot math.

    ``needs_sort=False`` drops the per-step stable argsort for segments
    whose steps statically carry <=1 valid message (valid slots are a
    prefix, so the sort is the identity there)."""
    assert mode == "scan" or not collect_events, \
        "event collection requires the serial message scan"
    spec = S.chain_spec(proto) if mode == "chain" else None
    chained = spec is not None

    def _wave_chain(net, p, msgs, valid_s):
        """Message phase as a CHAINED wave loop for ONE policy lane.

        The scatter-bound cost model of the batched wave loop (every wave
        re-scatters all ``cap`` slots) is turned inside out: row state is
        gathered ONCE per step into per-slot buffers, each wave runs the
        pure :func:`S._slot_compute` arithmetic on values read through the
        per-slot predecessor chain (``_row_chain``), and each row's final
        value is scattered back ONCE by its last-writer slot.  Per-wave
        work is dense vector math + two chain gathers — no scatters.

        Bit-identity with the serial scan holds by construction: a slot's
        chain input IS the value the serial path would gather (its row
        after the previous writer), and every update replicates the serial
        scatter arithmetic operand-for-operand (adds stay ``g + delta``,
        sets stay masked selects).  Dummy-row slots chain among themselves
        and land on the dummy row, which both paths already treat as
        garbage (masked adds of NaN deltas)."""
        links_s, dirs_s, nhops_s, t_inj_s, nbytes_s, _ = msgs
        cap, H = links_s.shape
        K = cap * H
        f64_keys, i64_keys = spec
        Lf, Li = len(f64_keys), len(i64_keys)
        kf = {k: i for i, k in enumerate(f64_keys)}
        ck = None
        if proto.kind == "coalesce":
            ck = ("coal_n", "coal_prev", "coal_release")
        elif proto.kind == "precoalesce":
            ck = ("pre_n", "pre_prev", "pre_release")
        active, lp, dp = S._slot_rows(links_s, dirs_s, nhops_s, valid_s,
                                      n_links)
        lpf, dpf, afl = lp.reshape(K), dp.reshape(K), active.reshape(K)
        predL, lastL = _row_chain(lpf)
        predD, lastD = _row_chain(dpf)

        if needs_sort:
            # per-hop chain predecessors (message index): a slot is ready
            # when every predecessor message has executed.  Same-message
            # predecessors (a route revisiting a link) are masked out —
            # hops of one message share a wave by definition.
            own = jnp.arange(cap, dtype=jnp.int32)[:, None]
            pmm = (predL // H).reshape(cap, H)
            hp = (predL != jnp.arange(K, dtype=jnp.int32)).reshape(cap, H) \
                & active & (pmm != own)
        else:
            pmm = jnp.zeros((cap, H), predL.dtype)
            hp = jnp.zeros((cap, H), bool)

        # one gather per dtype group: stacked row arrays -> slot views
        RF = jnp.stack([net[k] for k in f64_keys])          # (Lf, P)
        RI = jnp.stack([net[k] for k in i64_keys])          # (Li, P)
        GF = RF[:, lp]                                      # (Lf, cap, H)
        GD = net["dir_free"][dp]
        tpdt0 = net["pred"]["tpdt"][lp]     # read-only for chained kinds
        t_s = p["t_s"]
        tdst = jnp.maximum(p["t_dst"], t_s)

        def body(st):
            VF, VD, cI, delivery, lat, done = st
            # frontier membership == the order-preserving wave schedule:
            # ready slots whose chain predecessors have all executed
            member = ~done & jnp.where(hp, done[pmm], True).all(axis=1)
            act = active & member[:, None]
            inF = VF[:, predL].reshape((Lf, cap, H))
            inD = VD[predD].reshape((cap, H))
            g = {"free": inD, "last": inF[kf["last_end"]],
                 "dl": inF[kf["deadline"]], "dl2": inF[kf["deadline2"]]}
            if ck is not None:
                g["coal"] = (inF[kf[ck[0]]], inF[kf[ck[1]]],
                             inF[kf[ck[2]]])
            m = (links_s, dirs_s, nhops_s, t_inj_s, nbytes_s, member)
            ns = S._slot_compute(g, m, act, proto, pm, params=p)
            a = ns["a"]
            asleep, deep = ns["asleep"], ns["deep"]
            new_last = ns["new_last"]
            # new row values, replicating the serial scatter arithmetic
            # operand-for-operand: .add -> g + delta, .set -> masked select
            updF = [None] * Lf
            updF[kf["last_end"]] = g["last"] + (new_last - g["last"]) * a
            new_dl = jnp.where(act, new_last + tpdt0, g["dl"])
            updF[kf["deadline"]] = g["dl"] + (new_dl - g["dl"])
            updF[kf["deadline2"]] = jnp.where(act, new_dl + tdst, g["dl2"])
            updF[kf["time_wake"]] = inF[kf["time_wake"]] \
                + ns["wake_add"] * a
            updF[kf["time_sleep"]] = inF[kf["time_sleep"]] \
                + ns["sleep_add"] * a
            updF[kf["time_sleep2"]] = inF[kf["time_sleep2"]] \
                + ns["sleep2_add"] * a
            if ck is not None:
                updF[kf[ck[0]]], updF[kf[ck[1]]], updF[kf[ck[2]]] = \
                    ns["coal_new"]
            # int counters are pure commutative adds — no chaining needed:
            # record each slot's contribution once, scatter-add at the end
            contrib = jnp.stack([asleep & act, asleep & act,
                                 ~asleep & act, deep & act]
                                ).astype(jnp.int64).reshape((Li, K))
            updD = inD + jnp.maximum(ns["t_end"] - inD, 0.0) * a
            mK = jnp.repeat(member, H)
            VF = jnp.where(mK[None], jnp.stack(updF).reshape((Lf, K)), VF)
            cI = jnp.where(mK[None], contrib, cI)
            VD = jnp.where(mK, updD.reshape(K), VD)
            return (VF, VD, cI,
                    jnp.where(member, ns["delivery"], delivery),
                    jnp.where(member, ns["lat"], lat), done | member)

        VF, VD, cI, delivery, lat, _ = lax.while_loop(
            lambda st: ~st[5].all(), body,
            (GF.reshape((Lf, K)), GD.reshape(K),
             jnp.zeros((Li, K), jnp.int64),
             t_inj_s, jnp.zeros_like(t_inj_s), ~valid_s))

        # ONE scatter per dtype group: each row's last writer carries its
        # final value; every other slot redirects to the dummy row (already
        # garbage-tolerated by the serial path's masked scatters)
        idxL = jnp.where(afl & lastL, lpf, n_links)
        idxD = jnp.where(afl & lastD, dpf, 2 * n_links)
        RF = RF.at[:, idxL].set(VF)
        RI = RI.at[:, jnp.where(afl, lpf, n_links)].add(cI)
        net = dict(net, dir_free=net["dir_free"].at[idxD].set(VD))
        for i, k in enumerate(f64_keys):
            net[k] = RF[i]
        for i, k in enumerate(i64_keys):
            net[k] = RI[i]
        return net, delivery, lat

    def _wave_phase(net, p, msgs, valid_s, conf, order):
        """Message phase as a dynamic wave loop for ONE policy lane.

        Wave ids follow the ORDER-PRESERVING recurrence
        ``wave[i] = 1 + max(wave[j] : j conflicts i, j before i)`` (1-based
        over valid slots), solved by fixpoint iteration: conflicting pairs
        land in strictly increasing waves matching the injection sort, so
        every FSM row sees its messages in exactly the serial order."""
        links_s, dirs_s, nhops_s, t_inj_s, nbytes_s, _ = msgs
        n = valid_s.shape[0]
        if order is not None:
            conf_s = conf[order][:, order]
            pred = conf_s & (jnp.arange(n)[None, :] < jnp.arange(n)[:, None])

            def fixed(st):
                wv, _ = st
                nw = jnp.where(
                    valid_s,
                    jnp.where(pred, wv[None, :], 0).max(axis=1) + 1,
                    0).astype(jnp.int32)
                return nw, (nw != wv).any()

            wave, _ = lax.while_loop(lambda st: st[1], fixed,
                                     (valid_s.astype(jnp.int32),
                                      jnp.array(True)))
        else:
            # needs_sort=False: <=1 valid message, trivially one wave
            wave = valid_s.astype(jnp.int32)
        wmax = wave.max()

        def body(st):
            net, delivery, lat, w = st
            member = valid_s & (wave == w)
            net, (d, l, _ev) = S._message_step(
                net, (links_s, dirs_s, nhops_s, t_inj_s, nbytes_s, member),
                proto, pm, n_links, params=p)
            return (net, jnp.where(member, d, delivery),
                    jnp.where(member, l, lat), w + 1)

        # dynamic trip count = the step's realized wave width; under vmap
        # this lifts to the max over lanes and converged lanes run all-
        # masked (provably no-op) extra waves
        net, delivery, lat, _ = lax.while_loop(
            lambda st: st[3] <= wmax, body,
            (net, t_inj_s, jnp.zeros_like(t_inj_s), jnp.int32(1)))
        return net, delivery, lat

    def _prefix_phase(net, p, msgs, valid_s, nv):
        """Message phase as a dynamic loop over the step's VALID prefix.

        After the injection sort the valid slots are a prefix of length
        ``nv`` (the plan's per-step live count, ``xs["live"]``), while
        ``cap`` is the segment-wide bucket — ``BUCKET_MIN`` or a power of
        two, often several times larger.  The serial scan burns a full
        ``cap`` trip on provably no-op padding slots; this loop runs the
        SAME per-message body ``nv`` times and stops.  Skipped padding
        iterations only touch the dummy rows both paths treat as garbage
        (masked scatters of zero/NaN deltas), so results are bit-identical
        to the scan."""
        links_s, dirs_s, nhops_s, t_inj_s, nbytes_s, _ = msgs

        def body(st):
            net, delivery, lat, i = st
            m = tuple(lax.dynamic_index_in_dim(v, i, keepdims=False)
                      for v in (links_s, dirs_s, nhops_s, t_inj_s,
                                nbytes_s, valid_s))
            net, (d, l, _ev) = S._message_step(net, m, proto, pm, n_links,
                                               params=p)
            return (net, delivery.at[i].set(d), lat.at[i].set(l), i + 1)

        # padding slots never deliver (masked out of the ready scatter-max)
        # and carry exactly 0.0 latency in the scan too, so initializing
        # delivery = t_inj / lat = 0 reproduces the scan's outputs bitwise
        net, delivery, lat, _ = lax.while_loop(
            lambda st: st[3] < nv, body,
            (net, t_inj_s, jnp.zeros_like(t_inj_s), jnp.int32(0)))
        return net, delivery, lat

    def _lane(net, p, ready, lat_sum, lat_max, mx, extra):
        """Message phase of one step for ONE policy lane.  ``extra`` is the
        lane-invariant per-step operand of the chosen lowering: the
        conflict matrix (fallback chain mode), the live count (prefix
        mode), or None."""
        src, dst, nbytes, links, dirs, nhops, valid = mx
        t_inj = ready[src]
        if needs_sort:
            # stable sort, padding keyed to +inf: the valid prefix orders
            # exactly like the reference engine's host np.argsort
            order = jnp.argsort(jnp.where(valid, t_inj, jnp.inf),
                                stable=True)
            dst_s = dst[order]
            valid_s = valid[order]
            msgs = (links[order], dirs[order], nhops[order], t_inj[order],
                    nbytes[order], valid_s)
        else:
            # <=1 valid message per step: valid slots are a prefix and the
            # stable sort is the identity, so skip it (plan-time flag)
            order = None
            dst_s, valid_s = dst, valid
            msgs = (links, dirs, nhops, t_inj, nbytes, valid)

        if chained:
            net, delivery, lat = _wave_chain(net, p, msgs, valid_s)
            out = None
        elif mode == "chain":
            net, delivery, lat = _wave_phase(net, p, msgs, valid_s, extra,
                                             order)
            out = None
        elif mode == "prefix":
            net, delivery, lat = _prefix_phase(net, p, msgs, valid_s,
                                               extra)
            out = None
        else:
            def msg_step(net, m):
                net, (d, lat, ev) = S._message_step(net, m, proto, pm,
                                                    n_links, params=p)
                return net, ((d, lat, ev) if collect_events else (d, lat))

            net, out = lax.scan(msg_step, net, msgs)
            delivery, lat = out[0], out[1]
        ready = ready.at[dst_s].max(jnp.where(valid_s, delivery, -jnp.inf))
        lat_sum = lat_sum + lat.sum()
        lat_max = jnp.maximum(lat_max, lat.max())
        if collect_events:
            return net, ready, lat_sum, lat_max, out[2]
        return net, ready, lat_sum, lat_max

    def run(nets, params, ready, lat_sum, lat_max, part_mask, xs):
        B = ready.shape[0]

        def step(carry, x):
            nets, ready, lat_sum, lat_max = carry
            ready = ready + x["delta"][None]
            ev = None
            if cap:
                mx = (x["src"], x["dst"], x["nbytes"], x["links"],
                      x["dirs"], x["nhops"], x["valid"])

                def do(ops):
                    nets, ready, ls, lm = ops
                    extra = None
                    if mode == "chain" and not chained and needs_sort:
                        extra = _conflicts(x["links"], x["nhops"],
                                           x["valid"])
                    elif mode == "prefix":
                        extra = x["live"]
                    return jax.vmap(_lane,
                                    in_axes=(0, 0, 0, 0, 0, None, None))(
                        nets, params, ready, ls, lm, mx, extra)

                def skip(ops):
                    if not collect_events:
                        return ops
                    H = x["links"].shape[-1]
                    return ops + ((
                        jnp.full((B, cap, H), n_links, jnp.int32),
                        jnp.zeros((B, cap, H), jnp.float64),
                        jnp.zeros((B, cap, H), jnp.float64),
                        jnp.zeros((B, cap, H), bool)),)

                out = lax.cond(x["has_msgs"], do, skip,
                               (nets, ready, lat_sum, lat_max))
                if collect_events:
                    nets, ready, lat_sum, lat_max, ev = out
                else:
                    nets, ready, lat_sum, lat_max = out
            rmax = jnp.max(jnp.where(part_mask, ready, -jnp.inf), axis=-1)
            ready = jnp.where(x["barrier"] & part_mask, rmax[:, None], ready)
            return (nets, ready, lat_sum, lat_max), ev

        return lax.scan(step, (nets, ready, lat_sum, lat_max), xs)

    return run


@lru_cache(maxsize=None)
def _segment_runner(proto: Policy, pm: PowerModel, n_links: int, cap: int,
                    collect_events: bool, mode: str = "scan",
                    needs_sort: bool = True):
    """One jitted scan over a segment's steps; retraces per (S, B) shape."""
    return partial(jax.jit, donate_argnums=(0, 2, 3, 4))(
        _make_run(proto, pm, n_links, cap, collect_events, mode,
                  needs_sort))


@lru_cache(maxsize=None)
def _multi_segment_runner(proto: Policy, pm: PowerModel, n_links: int,
                          cap: int, mode: str = "scan",
                          needs_sort: bool = True):
    """The multi-trace runner: the per-trace program vmapped over a leading
    T axis.  ``params`` is shared across traces (in_axes None) — every
    trace lane replays the same stacked policy group — while the carry,
    participant mask and segment arrays are per-trace.  Retraces per
    (T, S, B) shape; programs are shared across stack groups with equal
    segment shapes."""
    run = _make_run(proto, pm, n_links, cap, collect_events=False,
                    mode=mode, needs_sort=needs_sort)
    return partial(jax.jit, donate_argnums=(0, 2, 3, 4))(
        jax.vmap(run, in_axes=(0, None, 0, 0, 0, 0, 0)))


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------


@jax.jit
def _participant_max(mask, ready):
    """Per-lane makespan: max ``ready`` over participants.  Jitted so the
    -inf fill is a compile-time constant (keeps warm replays transfer-free)."""
    return jnp.max(jnp.where(mask, ready, -jnp.inf), axis=-1)


def init_lanes(pols, plan):
    """Lane setup (the only host->device traffic of a replay): canonical
    proto, stacked params, and the initial scan carry — batched net state,
    zeroed per-node ``ready`` clocks, zeroed latency accumulators."""
    proto = canonical_proto(pols[0])
    params = stack_params(pols)
    nets = jax.vmap(
        lambda p: S.init_net(plan.n_links, proto, params=p))(params)
    B = next(iter(params.values())).shape[0]
    carry = (nets, jnp.zeros((B, plan.n_nodes), jnp.float64),
             jnp.zeros((B,), jnp.float64), jnp.zeros((B,), jnp.float64))
    return proto, params, carry


def run_segments(plan, proto, params, pm, carry, collect_events=False):
    """Execute every plan segment, carrying all state on device.

    ``carry`` is ``init_lanes``'s (nets, ready, lat_sum, lat_max).  Host
    work per segment is ONE jitted-call dispatch — no transfers, no sorts,
    no padding (pinned by tests/test_plan.py under a transfer guard).
    Returns device values ``(nets, t_end (B,), lat_sum (B,), lat_max (B,),
    seg_events)``.
    """
    seg_events = [] if collect_events else None
    for seg in plan.segments:
        md, ns = _seg_flags(seg, proto, collect_events)
        run = _segment_runner(proto, pm, plan.n_links, seg.cap,
                              collect_events, md, ns)
        carry, evs = run(carry[0], params, carry[1], carry[2], carry[3],
                         plan.part_mask, seg.xs)
        if collect_events and seg.cap:
            seg_events.append((seg, evs))
    nets, ready, lat_sum, lat_max = carry
    if plan.has_participants:
        t_end = _participant_max(plan.part_mask, ready)
    else:
        t_end = lat_sum * 0.0
    return nets, t_end, lat_sum, lat_max, seg_events


def replay_plan(plan, pols, pm, collect_events=False):
    """One-stop compiled replay: init lanes, run segments, read back.

    Returns ``(nets, t_end, lat_sum, lat_max, seg_events)`` with the
    scalar accumulators as host numpy (B,) arrays.
    """
    proto, params, carry = init_lanes(pols, plan)
    nets, t_end, lat_sum, lat_max, seg_events = run_segments(
        plan, proto, params, pm, carry, collect_events)
    return (nets, np.asarray(t_end), np.asarray(lat_sum),
            np.asarray(lat_max), seg_events)


# ---------------------------------------------------------------------------
# Multi-trace driver: a (traces x policies) grid in one program per segment
# ---------------------------------------------------------------------------


@jax.jit
def _participant_max_multi(mask, ready):
    """Per-(trace, lane) makespan: max ``ready`` over each trace's own
    participants.  mask (T, n_nodes), ready (T, B, n_nodes) -> (T, B)."""
    return jnp.max(jnp.where(mask[:, None, :], ready, -jnp.inf), axis=-1)


@lru_cache(maxsize=None)
def _multi_init(proto: Policy, n_links: int, n_nodes: int, T: int):
    """Jitted (T, B) carry constructor — ONE program per (proto, T, B)
    instead of a spray of eager broadcast/zeros ops, keeping the grid
    path's compile count bounded by its segment programs."""
    @jax.jit
    def init(params):
        nets1 = jax.vmap(
            lambda p: S.init_net(n_links, proto, params=p))(params)
        B = next(iter(params.values())).shape[0]
        nets = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (T,) + x.shape), nets1)
        return (nets, jnp.zeros((T, B, n_nodes), jnp.float64),
                jnp.zeros((T, B), jnp.float64), jnp.zeros((T, B), jnp.float64))
    return init


def init_lanes_multi(pols, batch):
    """Lane setup for a :class:`~repro.traffic.plan.PlanBatch`: the B-lane
    initial state of ``init_lanes`` replicated along a leading T trace axis
    (initial net state depends only on the policy, so every trace lane
    starts from the same bits as its single-trace replay)."""
    proto = canonical_proto(pols[0])
    params = stack_params(pols)
    carry = _multi_init(proto, batch.n_links, batch.n_nodes,
                        batch.n_traces)(params)
    return proto, params, carry


def run_segments_multi(batch, proto, params, pm, carry):
    """Execute every segment of a :class:`PlanBatch`, carrying the whole
    (T, B, ...) grid state on device.  Host work per segment is one
    jitted-call dispatch, exactly like the single-trace path.  Returns
    device ``(nets, t_end (T, B), lat_sum (T, B), lat_max (T, B))``."""
    for seg in batch.segments:
        md, ns = _seg_flags(seg, proto)
        run = _multi_segment_runner(proto, pm, batch.n_links, seg.cap,
                                    md, ns)
        carry, _ = run(carry[0], params, carry[1], carry[2], carry[3],
                       batch.part_mask, seg.xs)
    nets, ready, lat_sum, lat_max = carry
    t_end = _participant_max_multi(batch.part_mask, ready)
    return nets, t_end, lat_sum, lat_max


def replay_plans(batch, pols, pm):
    """Compiled (traces x policies) grid replay over a ``PlanBatch``.

    Returns ``(nets, t_end, lat_sum, lat_max)`` where the net state keeps
    its (T, B, ...) leading axes on device and the scalar accumulators come
    back as host numpy (T, B) arrays.  Per-(t, b) cell results are
    bit-identical to that trace's own single-trace ``replay_plan`` —
    the multi runner is the same program vmapped over T.
    """
    proto, params, carry = init_lanes_multi(pols, batch)
    nets, t_end, lat_sum, lat_max = run_segments_multi(
        batch, proto, params, pm, carry)
    t_end = np.asarray(t_end)
    # traces with no participants have an all-False mask row (-inf max)
    t_end = np.where(batch.has_participants[:, None], t_end, 0.0)
    return nets, t_end, np.asarray(lat_sum), np.asarray(lat_max)


def events_to_host(plan, seg_events):
    """Lower collected events to the classic per-message-step host list
    ``[(link, t_start, t_end), ...]`` (active hops only, replay order).

    Only the B=1 (serial) path collects events; lane 0 is extracted.
    """
    out = []
    for seg, evs in seg_events:
        lp, ts, te, act = (np.asarray(x) for x in evs)   # (S, B, cap, H)
        for i in range(seg.n_steps):
            if not seg.host_has_msgs[i]:
                continue
            m = act[i, 0]
            out.append((lp[i, 0][m], ts[i, 0][m], te[i, 0][m]))
    return out
