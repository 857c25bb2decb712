"""Coupled network power simulator: a ``lax.scan`` over messages.

Each scan step walks one message along its (<=5-hop) minimal route with a
cut-through timing model, checks/updates every traversed link's EEE FSM
(PDT timers, the Fast Wake -> Deep Sleep demotion ladder, coalescing
deferrals, wake penalties — DESIGN.md §6), feeds the PerfBound predictors,
and integrates per-link wake/sleep/deep-sleep time for energy accounting.

TPU-native layout: per-hop state reads are gathered up front (a message's
route never repeats a link), the 5-hop time chain runs on registers, and all
state writes land as batched scatters.  A dummy row (index P) absorbs writes
from padded/inactive hops so scatters never race.

Execution-time semantics come from the phase-structured replay
(`simulate_trace`): per-node ready times advance across trace steps with
message-delivery dependencies — makespan overhead, packet latency, and energy
are measured exactly as in §4 of the paper.

Replay runs as a compiled two-stage pipeline (DESIGN.md §2): the trace is
compiled ONCE per topology into a device-resident ``TracePlan``
(``repro.traffic.plan``) and executed as ``lax.scan`` over plan steps
(``repro.core.replay``) with the per-node ``ready`` clocks carried on
device.  ``simulate_trace`` is a thin wrapper over that executor (the B=1
case of the batched sweep); the original host step-loop survives as
``simulate_trace_reference`` — the semantic oracle the equivalence suite
(``tests/test_plan.py``) pins the compiled path against.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial, lru_cache

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import ieee
from repro.core import perfbound as pb
from repro.core.eee import Policy, PowerModel, policy_params
from repro.traffic.plan import compile_plan, pad_message_table

MAX_HOPS = 5


# ---------------------------------------------------------------------------
# Clocks
# ---------------------------------------------------------------------------

#: Whether kinds that record inactivity gaps keep their clocks as float64
#: bit patterns (``ieee.ExactClock``); ``None`` follows the process's
#: backend: exact on the TPU, plain float64 (the same bits) elsewhere.
EXACT_CLOCKS = None


def clock_of(policy: Policy):
    """The clock arithmetic of a policy's replay (DESIGN.md §2).

    A kind that records inactivity gaps (PerfBound and its variants,
    ``predict``, ``record_hist``) decides on ``t_avail - last``, where a
    queued message's gap is zero or an IEEE rounding residue: the TPU, whose
    float64 is a pair of float32s, can reproduce neither, so there those
    kinds carry every clock as the float64's bit pattern with IEEE
    arithmetic (``ieee.ExactClock``).  Every other kind, and every kind on a
    backend with a float64 unit, keeps plain float64."""
    records = policy.adaptive or policy.record_hist
    exact = EXACT_CLOCKS if EXACT_CLOCKS is not None \
        else jax.default_backend() == "tpu"
    if exact and records and policy.kind not in ("coalesce", "precoalesce"):
        return ieee.ExactClock
    return ieee.FloatClock


def clock_param_bits(policy: Policy, cols: list) -> dict:
    """The host bit patterns an exact-clock replay of ``policy``'s group
    reads (``ieee.param_bits``), for the parameter dicts ``cols``: the
    clock parameters, and the bin edges of a linear histogram."""
    linear = not policy.hist_log_bins
    return ieee.param_bits(cols, policy.hist_bins if linear else 0)


# ---------------------------------------------------------------------------
# Network state
# ---------------------------------------------------------------------------


def init_net(n_links, policy: Policy, params=None):
    P = n_links + 1  # +1 dummy row absorbing masked writes
    # PDT timers are armed at t=0 (ports start awake, counting down) — the
    # same convention as the decoupled per-port replay, so both paths see
    # identical first-arrival semantics.  The demotion deadline sits a
    # (clamped) t_dst past the sleep deadline; for single-state kinds
    # t_dst = +inf keeps the deep row of the FSM unreachable.
    p = pb._params(policy, params)
    C = clock_of(policy)
    first = pb.initial_tpdt_param(policy)
    dl0 = C.const(float("inf")) if first is None else C.param(p, first)
    dl2_0 = C.add(dl0, C.maximum(C.param(p, "t_dst"), C.param(p, "t_s")))
    net = {
        "dir_free": jnp.zeros((2 * n_links + 1,), C.dtype),
        "last_end": jnp.zeros((P,), C.dtype),
        "deadline": jnp.full((P,), dl0, C.dtype),
        "deadline2": jnp.full((P,), dl2_0, C.dtype),
        "time_wake": jnp.zeros((P,), jnp.float64),
        "time_sleep": jnp.zeros((P,), jnp.float64),
        "time_sleep2": jnp.zeros((P,), jnp.float64),
        "n_wake": jnp.zeros((P,), jnp.int64),
        "n_hit": jnp.zeros((P,), jnp.int64),
        "n_miss": jnp.zeros((P,), jnp.int64),
        "n_deep": jnp.zeros((P,), jnp.int64),
        "pred": pb.init_state(P, policy, params),
    }
    if policy.kind == "coalesce":
        # per-port coalescing-cycle carry: frames absorbed by the current
        # sleep cycle, the previous cycle's final count (the early-wake
        # burst-size estimate), and the current cycle's wake-completion time
        net["coal_n"] = jnp.zeros((P,), jnp.float64)
        net["coal_prev"] = jnp.zeros((P,), jnp.float64)
        net["coal_release"] = jnp.zeros((P,), jnp.float64)
    if policy.kind == "precoalesce":
        # hold-at-source cycle carry: same structure as coalescing, but the
        # cycle lives on the INJECTION link only — downstream ports see the
        # already-batched bursts and keep plain dual-ladder FSMs
        net["pre_n"] = jnp.zeros((P,), jnp.float64)
        net["pre_prev"] = jnp.zeros((P,), jnp.float64)
        net["pre_release"] = jnp.zeros((P,), jnp.float64)
    return net


# ---------------------------------------------------------------------------
# One message
# ---------------------------------------------------------------------------


def _slot_rows(links, dirs, nhops, valid, n_links):
    """Per-slot row ids: (active mask, link row ``lp``, directed row ``dp``).
    Inactive slots land on the dummy rows (``n_links`` / ``2*n_links``)."""
    H = links.shape[-1]           # route width (Megafly 5, fat-tree 6, ...)
    active = (jnp.arange(H) < nhops[..., None]) & valid[..., None] \
        & (links >= 0)
    lp = jnp.where(active, links, n_links)                 # dummy row when off
    dp = jnp.where(active, 2 * links + dirs, 2 * n_links)
    return active, lp, dp


def _slot_compute(g, msg, active, policy: Policy, pm: PowerModel,
                  params=None):
    """FSM + energy arithmetic of one message (or a batch of link-disjoint
    messages) as a PURE elementwise function of gathered row state.

    ``g`` carries the slot views (same leading shape as ``links``):
    ``free`` (directed occupancy), ``last``/``dl``/``dl2`` (accounting
    frontier + FSM deadlines) and, for the coalescing kinds, the ``coal``
    triple.  Each slot's outputs depend only on its own message's slots
    and its gathered inputs — the serial scatter path and the chained
    wavefront path (replay.py) both consume this, which is what makes
    their results bit-identical by construction (DESIGN.md §10)."""
    links, dirs, nhops, t_inj, nbytes, valid = msg
    H = links.shape[-1]
    p = pb._params(policy, params)
    # clock arithmetic (``clock_of``): plain float64, or float64 bit
    # patterns with IEEE roundings; the energy integration below reads
    # the clocks' float64 values
    C = clock_of(policy)
    sync = C.param(p, "sync_overhead")
    t_w = C.add(C.param(p, "t_w"), sync)
    t_s = C.param(p, "t_s")
    # FSM row 2 (Deep Sleep): reachable only past ``deadline2``, which
    # single-state kinds pin to +inf (t_dst = inf) — every row-2 branch
    # below then selects the row-1 value, reproducing the single-state
    # arithmetic bit for bit.
    t_w2 = C.add(C.param(p, "t_w2"), sync)
    t_s2 = C.param(p, "t_s2")
    zero = C.const(0.0)
    coal = policy.kind == "coalesce"
    pre = policy.kind == "precoalesce"
    defer_on = coal or pre
    t_ser = C.tser(nbytes, pm.link_bandwidth)

    free = g["free"]
    last = g["last"]
    dl = g["dl"]
    dl2 = g["dl2"]
    if defer_on:
        # wake deferral for the frame that would wake a sleeping port:
        # full max_delay, scaled down when the previous cycle's burst
        # overran the queue bound (rate estimate of the max_frames
        # trigger).  At a miss the just-ended cycle's count still sits in
        # coal_n (it rolls into coal_prev below), so the freshest burst
        # estimate is coal_n when non-zero, else the rolled coal_prev.
        # precoalesce runs the SAME cycle machinery with its own knobs
        # (hold_delay/hold_frames) on separate carries, restricted below
        # to the injection hop.
        d_delay = p["max_delay"] if coal else p["hold_delay"]
        d_frames = p["max_frames"] if coal else p["hold_frames"]
        coal_n_g, coal_prev_g, coal_release_g = g["coal"]
        prev_burst = jnp.where(coal_n_g > 0, coal_n_g, coal_prev_g)
        defer_full = jnp.where(
            d_frames > 1.0,
            d_delay * d_frames
            / jnp.maximum(prev_burst, d_frames), 0.0)
        # hold-at-source: frames queue at the injection link (hop 0) only;
        # downstream hops never defer
        at_src = jnp.broadcast_to((jnp.arange(H) == 0) if pre
                                  else jnp.ones((H,), bool), active.shape)
        defer_amt = jnp.where(at_src, defer_full, 0.0)

    def _fsm(ta, dl_h, dl2_h, defer_h):
        """One port's FSM read at raw arrival ``ta``: (asleep, deep,
        in_down, in_down2, effective arrival, wake penalty)."""
        asleep = C.ge(ta, dl_h)
        tae = ta + jnp.where(asleep, defer_h, 0.0) if defer_on else ta
        deep = C.ge(tae, dl2_h)
        down_end, down2_end = C.add(dl_h, t_s), C.add(dl2_h, t_s2)
        in_down = asleep & C.lt(tae, down_end)
        in_down2 = deep & C.lt(tae, down2_end)
        pen_fast = C.add(jnp.where(in_down, C.sub(down_end, tae), zero), t_w)
        pen_deep = C.add(jnp.where(in_down2, C.sub(down2_end, tae), zero),
                         t_w2)
        pen = jnp.where(asleep, jnp.where(deep, pen_deep, pen_fast), zero)
        return asleep, deep, in_down, in_down2, tae, pen

    # ---- unrolled 5-hop time chain (register-only) -----------------------
    t_head = t_inj
    t_avail = jnp.zeros(active.shape, C.dtype)
    t_start = jnp.zeros(active.shape, C.dtype)
    if defer_on:
        # pre-occupancy arrival per hop: the moment the frame reaches the
        # port's queue, BEFORE waiting for the link to free — the time the
        # coalescing-cycle join test must use (a frame queued behind the
        # waking head is serviced after the release, but it joined before)
        t_arr = jnp.zeros(active.shape, jnp.float64)
    delivery = t_inj
    hop = C.const(pm.switch_latency)
    for h in range(H):
        ta = C.maximum(t_head, free[..., h])
        _, _, _, _, tae, pen = _fsm(ta, dl[..., h], dl2[..., h],
                                    defer_amt[..., h] if defer_on else 0.0)
        ts_ = C.add(tae, pen)
        te_ = C.add(ts_, t_ser)
        t_avail = t_avail.at[..., h].set(ta)
        t_start = t_start.at[..., h].set(ts_)
        if defer_on:
            t_arr = t_arr.at[..., h].set(t_head)
        t_head = jnp.where(active[..., h], C.add(ts_, hop), t_head)
        delivery = jnp.where(active[..., h], te_, delivery)

    t_end = C.add(t_start, t_ser[..., None])
    asleep, deep, in_down, in_down2, tae, _ = _fsm(
        t_avail, dl, dl2, defer_amt if defer_on else 0.0)
    gap = C.sub(t_avail, last)
    new_last = C.maximum(last, t_end)

    # ---- energy time integration (frontier scheme) ------------------------
    # ``last_end`` is the accounting frontier: everything before it is
    # already integrated.  awake case: the whole span frontier..t_end is at
    # wake power (idle-awake + transmission); overlap with the opposite
    # direction can make t_end < frontier, in which case nothing is added.
    # asleep case: PDT tail (frontier..deadline) + down transition(s) + wake
    # transition + transmission at wake power; the span between transitions
    # sleeps at the row-1 floor and — past the demotion deadline and its
    # second down transition — at the row-2 floor (zero spans if the packet
    # lands during a down transition).
    v = C.val
    dlv, dl2v, lastv, taev = v(dl), v(dl2), v(last), v(tae)
    t_sv, t_s2v, t_wv, t_w2v = v(t_s), v(t_s2), v(t_w), v(t_w2)
    t_serv = v(t_ser)
    wake_fast = (dlv - lastv) + t_sv + t_wv + t_serv[..., None]
    wake_deep = (dlv - lastv) + t_sv + t_s2v + t_w2v + t_serv[..., None]
    wake_add = jnp.where(asleep,
                         jnp.where(deep, wake_deep, wake_fast),
                         jnp.maximum(v(new_last) - lastv, 0.0))
    sleep_add = jnp.where(asleep & ~in_down,
                          jnp.where(deep, dl2v - (dlv + t_sv),
                                    jnp.maximum(taev - (dlv + t_sv), 0.0)),
                          0.0)
    sleep2_add = jnp.where(deep & ~in_down2,
                           jnp.maximum(taev - (dl2v + t_s2v), 0.0), 0.0)
    a = active.astype(jnp.float64)

    # clocks in the clock's representation (t_avail, t_start, t_end,
    # new_last, delivery); gap, t_now and lat as float64 for the
    # predictors and the latency sums
    out = dict(
        active=active, a=a, asleep=asleep, deep=deep, gap=v(gap),
        gap_bits=None if C is ieee.FloatClock else gap,
        t_avail=t_avail, t_start=t_start, t_end=t_end, new_last=new_last,
        t_now=v(t_avail), wake_add=wake_add, sleep_add=sleep_add,
        sleep2_add=sleep2_add, delivery=delivery,
        lat=jnp.where(valid & (nhops > 0), v(delivery) - v(t_inj), 0.0),
    )
    if defer_on:
        # precoalesce: the cycle state advances only at the injection hop
        # (the at_src mask); downstream rows write their gathered values
        # back unchanged
        miss = asleep & active & at_src
        join = active & at_src & ~asleep & (coal_n_g > 0) \
            & (t_arr <= coal_release_g)
        roll = jnp.where(coal_n_g > 0, coal_n_g, coal_prev_g)
        out["coal_new"] = (
            jnp.where(miss, 1.0,
                      jnp.where(join, coal_n_g + 1.0, coal_n_g)),
            jnp.where(miss, roll, coal_prev_g),
            jnp.where(miss, t_start, coal_release_g),
        )
    return out


def _shift_rows(x, idx, delta, C):
    """Rows ``idx`` of a clock array moved by ``delta``, in the clock's
    arithmetic.  Bit patterns cannot scatter-add: they are gathered,
    added and set, which is the same where ``idx`` repeats only the
    dummy row (one message's hops; a wave's link-disjoint messages) and
    every masked slot adds zero there."""
    if C is ieee.FloatClock:
        return x.at[idx].add(delta)
    return x.at[idx].set(C.add(x[idx], delta))


def _message_step(net, msg, policy: Policy, pm: PowerModel, n_links: int,
                  params=None):
    """Advance the net state by one message — or, when the message arrays
    carry a leading batch axis (links ``(m, H)``, scalars ``(m,)``), by a
    whole *wave* of link-disjoint messages at once.  Disjoint routes make
    every gather read rows no other wave member writes and every scatter
    land on distinct rows (the dummy row only ever absorbs masked no-op
    writes), so the batched application is bit-identical to applying the
    members serially in any order (DESIGN.md §10)."""
    links, dirs, nhops, t_inj, nbytes, valid = msg
    p = pb._params(policy, params)
    C = clock_of(policy)
    coal = policy.kind == "coalesce"
    pre = policy.kind == "precoalesce"
    defer_on = coal or pre
    active, lp, dp = _slot_rows(links, dirs, nhops, valid, n_links)

    g = {
        "free": net["dir_free"][dp],
        "last": net["last_end"][lp],
        "dl": net["deadline"][lp],
        "dl2": net["deadline2"][lp],
    }
    tpdt_prev = net["pred"]["tpdt"][lp]
    if defer_on:
        ck = ("coal_n", "coal_prev", "coal_release") if coal \
            else ("pre_n", "pre_prev", "pre_release")
        g["coal"] = (net[ck[0]][lp], net[ck[1]][lp], net[ck[2]][lp])

    ns = _slot_compute(g, msg, active, policy, pm, params)
    a = ns["a"]
    asleep, deep, gap = ns["asleep"], ns["deep"], ns["gap"]
    t_start, t_end = ns["t_start"], ns["t_end"]
    new_last, dl, dl2 = ns["new_last"], g["dl"], g["dl2"]

    net = dict(
        net,
        time_wake=net["time_wake"].at[lp].add(ns["wake_add"] * a),
        time_sleep=net["time_sleep"].at[lp].add(ns["sleep_add"] * a),
        time_sleep2=net["time_sleep2"].at[lp].add(ns["sleep2_add"] * a),
        n_wake=net["n_wake"].at[lp].add((asleep & active).astype(jnp.int64)),
        n_miss=net["n_miss"].at[lp].add((asleep & active).astype(jnp.int64)),
        n_hit=net["n_hit"].at[lp].add((~asleep & active).astype(jnp.int64)),
        n_deep=net["n_deep"].at[lp].add((deep & active).astype(jnp.int64)),
    )

    # ---- coalescing-cycle bookkeeping -------------------------------------
    if defer_on:
        new_n, new_prev, new_release = ns["coal_new"]
        net[ck[1]] = net[ck[1]].at[lp].set(new_prev)
        net[ck[0]] = net[ck[0]].at[lp].set(new_n)
        net[ck[2]] = net[ck[2]].at[lp].set(new_release)

    # ---- occupancy / transmission-end bookkeeping -------------------------
    if C is ieee.FloatClock:
        free_step = jnp.maximum(t_end - g["free"], 0.0) * a
        last_step = (new_last - g["last"]) * a
    else:
        zero = C.const(0.0)
        free_step = jnp.where(active, C.sub(C.maximum(g["free"], t_end),
                                            g["free"]), zero)
        last_step = jnp.where(active, C.sub(new_last, g["last"]), zero)
    net["dir_free"] = _shift_rows(net["dir_free"], dp, free_step, C)
    net["last_end"] = _shift_rows(net["last_end"], lp, last_step, C)

    # ---- predictors --------------------------------------------------------
    H = links.shape[-1]
    pred = net["pred"]
    if policy.adaptive or policy.record_hist:
        pred = pb.record_gaps(pred, lp, gap, ns["t_now"], active, policy, p,
                              gap_bits=ns.get("gap_bits"))
        pred = pb.record_hops(pred, lp, nhops[..., None] - jnp.arange(H),
                              active, policy)
    if policy.kind == "perfbound_correct":
        ratio = gap / jnp.maximum(tpdt_prev, 1e-12)
        pred = pb.record_outcomes(pred, lp, asleep, ratio, active, policy)
    if policy.adaptive:
        if policy.kind == "perfbound_dual":
            new_tpdt, new_tdst = pb.compute_tpdt_tdst(
                pred, lp, C.val(t_end), p["t_w"], policy, p)
            pred = dict(pred, t_dst=pred["t_dst"].at[lp].set(
                jnp.where(active, new_tdst, pred["t_dst"][lp])))
        elif policy.kind == "predict":
            new_tpdt, new_tdst, new_ewma = pb.forecast_update(
                pred, lp, gap, active, policy, p)
            pred = dict(
                pred,
                t_dst=pred["t_dst"].at[lp].set(
                    jnp.where(active, new_tdst, pred["t_dst"][lp])),
                ewma=pred["ewma"].at[lp].set(
                    jnp.where(active, new_ewma, pred["ewma"][lp])))
        else:
            new_tpdt = pb.compute_tpdt(pred, lp, C.val(t_end), p["t_w"],
                                       policy, p)
        pred = dict(pred, tpdt=pred["tpdt"].at[lp].set(
            jnp.where(active, new_tpdt, pred["tpdt"][lp])))
    net["pred"] = pred

    # deadline = end of PDT countdown after the latest transmission;
    # deadline2 = the demotion point a (clamped) t_dst further out
    tpdt_now = C.of(net["pred"]["tpdt"][lp])
    new_dl = jnp.where(active, C.add(new_last, tpdt_now), dl)
    net["deadline"] = _shift_rows(net["deadline"], lp, C.sub(new_dl, dl), C)
    tdst_now = C.of(net["pred"]["t_dst"][lp]) \
        if policy.kind in ("perfbound_dual", "predict") \
        else C.param(p, "t_dst")
    new_dl2 = jnp.where(
        active, C.add(new_dl, C.maximum(tdst_now, C.param(p, "t_s"))), dl2)
    # masked SET, not scatter-add: adaptive t_dst legitimately swings
    # between +inf ("never demote") and finite, and inf - inf through an
    # add would latch the row at NaN, silently disabling demotion forever
    net["deadline2"] = net["deadline2"].at[lp].set(new_dl2)

    events = (lp, C.val(t_start), C.val(t_end), active)
    return net, (ns["delivery"], ns["lat"], events)


def chain_spec(policy: Policy):
    """Row-state layout for the CHAINED wavefront executor (replay.py):
    ``(f64 lp-keyed keys, i64 lp-keyed keys)`` — every per-link row array
    the message phase reads or writes, excluding ``dir_free`` (dp-keyed,
    threaded separately) and ``pred.tpdt`` (read-only for these kinds).

    Returns ``None`` for the adaptive / histogram-recording kinds: their
    predictor state (histogram matrices, ring buffers, shift registers) is
    not threaded through the chain buffers, so those protos fall back to
    the scatter-per-wave batched loop."""
    if policy.adaptive or policy.record_hist:
        return None
    f64 = ["last_end", "deadline", "deadline2",
           "time_wake", "time_sleep", "time_sleep2"]
    if policy.kind == "coalesce":
        f64 += ["coal_n", "coal_prev", "coal_release"]
    if policy.kind == "precoalesce":
        f64 += ["pre_n", "pre_prev", "pre_release"]
    i64 = ["n_wake", "n_miss", "n_hit", "n_deep"]
    return tuple(f64), tuple(i64)


@lru_cache(maxsize=None)
def _compiled_chunk(policy: Policy, pm: PowerModel, n_links: int,
                    collect_events: bool):
    C = clock_of(policy)

    @partial(jax.jit, donate_argnums=(0,))
    def run(net, msgs, params):
        if msgs[3].dtype != C.dtype:          # float64 injection times
            msgs = msgs[:3] + (C.of(msgs[3]),) + msgs[4:]

        def step(net, m):
            net, (d, lat, ev) = _message_step(net, m, policy, pm, n_links,
                                              params=params)
            out = (C.val(d), lat, ev) if collect_events else (C.val(d), lat)
            return net, out
        return lax.scan(step, net, msgs)
    return run


def sim_chunk(net, msgs, policy, pm, n_links, collect_events=False):
    """msgs: tuple of arrays (links (M,5), dirs, nhops, t_inj, bytes, valid).

    The policy's numerics enter as traced operands, as in the compiled
    replay: baked in as constants, XLA would rewrite ``gap / bin_width``
    into a multiply by the rounded reciprocal and bin a gap on a bin edge
    one bin lower than the replay does."""
    pp = policy_params(policy)
    params = {k: jnp.float64(v) for k, v in pp.items()}
    if clock_of(policy) is ieee.ExactClock:
        params.update({k: v[0] for k, v in
                       clock_param_bits(policy, [pp]).items()})
    return _compiled_chunk(policy, pm, n_links, collect_events)(
        net, msgs, params)


# ---------------------------------------------------------------------------
# Close-out + energy summary
# ---------------------------------------------------------------------------


def _as_float(x):
    """Clock values as float64, whichever clock kept them."""
    x = jnp.asarray(x)
    return ieee.value(x) if x.dtype == jnp.uint64 else x


def _close_out(net, t_end_sim, t_s, t_s2, n_links: int):
    """``close_out`` on the row-1 and row-2 down-transition times ``t_s``
    and ``t_s2``: plain numbers or traced operands alike."""
    # jnp inputs throughout: raw numpy would warn on the (masked-away)
    # inf-inf deep spans of never-woken links
    last = _as_float(net["last_end"][:n_links])
    dl = _as_float(net["deadline"][:n_links])
    dl2 = _as_float(net["deadline2"][:n_links])
    t_end_sim = jnp.maximum(t_end_sim, last.max())
    sleeps = dl + t_s < t_end_sim
    deeps = dl2 + t_s2 < t_end_sim
    # elapsed part of the second down transition (wake power, like every
    # transition): full t_s2 once demoted, partial if the sim ends
    # mid-transition, 0 for single-state rows (dl2 = +inf)
    down2 = jnp.clip(t_end_sim - dl2, 0.0, t_s2)
    wake_extra = jnp.where(
        sleeps, (dl - last) + t_s + down2, t_end_sim - last)
    sleep_extra = jnp.where(
        sleeps, jnp.where(deeps, dl2 - (dl + t_s),
                          jnp.minimum(t_end_sim, dl2) - dl - t_s), 0.0)
    sleep2_extra = jnp.where(deeps, t_end_sim - dl2 - t_s2, 0.0)
    return (net["time_wake"][:n_links] + jnp.maximum(wake_extra, 0.0),
            net["time_sleep"][:n_links] + jnp.maximum(sleep_extra, 0.0),
            net["time_sleep2"][:n_links] + jnp.maximum(sleep2_extra, 0.0))


def close_out(net, t_end_sim, policy: Policy, n_links: int):
    """Integrate every link's tail (last transmission .. end of sim) at the
    FSM row it ends in: awake, row-1 sleep past ``deadline``, row-2 sleep
    past ``deadline2`` (never reached by single-state kinds).  Returns
    (time_wake, time_sleep, time_sleep2)."""
    return _close_out(net, t_end_sim, policy.state.t_s, policy.deep.t_s,
                      n_links)


#: What ``lane_sums`` reduces each lane's net state to, in this order: the
#: link-summed close-out times, the last transmission end, and the
#: link-summed FSM counters (as float64, exact below 2**53).
LANE_SUMS = ("time_wake", "time_sleep", "time_sleep2", "last_end_max",
             "n_wake", "n_hit", "n_miss", "n_deep")
_COUNTERS = ("n_wake", "n_hit", "n_miss", "n_deep")
_REDUCED = ("last_end", "deadline", "deadline2", "time_wake", "time_sleep",
            "time_sleep2") + _COUNTERS


def _lane_sums(net, t_end, t_s, t_s2, n_links):
    tw, ts_, ts2 = _close_out(net, t_end, t_s, t_s2, n_links)
    return jnp.stack(
        [tw.sum(), ts_.sum(), ts2.sum(),
         _as_float(net["last_end"][:n_links]).max()]
        + [net[k][:n_links].sum().astype(jnp.float64) for k in _COUNTERS])


@partial(jax.jit, static_argnames=("n_links",))
def _reduce_lanes(net, times, n_links):
    """One program per (lane shape, n_links): ``_lane_sums`` vmapped over
    the lane axes of ``times``, each lane's (end time, t_s, t_s2) stacked
    ahead of them so that a group's operands cross in one transfer."""
    f = partial(_lane_sums, n_links=n_links)
    for _ in range(times.ndim - 1):
        f = jax.vmap(f)
    return f(net, *times)


def lane_sums(nets, t_end, pols, n_links: int) -> np.ndarray:
    """Reduce every lane of a replay's net state to its ``LANE_SUMS`` on
    the device and bring back that array alone: host float64 of shape
    ``t_end.shape + (len(LANE_SUMS),)``.

    ``nets`` leaves carry the lane axes of ``t_end`` (host end times:
    ``(T, B)``, ``(B,)`` or ``()`` for one unbatched net) before the link
    axis; ``pols`` are the B policies of the last lane axis (one for an
    unbatched net).  Their down-transition times enter as traced operands,
    as the replay's parameters do (see ``sim_chunk``), so every group of
    one lane shape shares one program."""
    t_end = np.asarray(t_end, np.float64)
    cols = [policy_params(p) for p in pols]

    def lanewise(key):
        return np.array([c[key] for c in cols]).reshape(t_end.shape[-1:])
    times = np.stack(np.broadcast_arrays(t_end, lanewise("t_s"),
                                         lanewise("t_s2")))
    return np.asarray(_reduce_lanes({k: nets[k] for k in _REDUCED}, times,
                                    n_links))


@dataclass
class SimResult:
    makespan: float
    mean_latency: float
    max_latency: float
    n_messages: int
    link_energy: float
    switch_energy: float
    node_energy: float
    total_energy: float
    asleep_frac: float          # mean fraction of time links spent asleep
    deep_frac: float            # fraction of link time in the deep FSM row
    n_wake_transitions: int
    hits: int
    misses: int
    deep_misses: int            # arrivals that found their port demoted

    def as_dict(self):
        return dataclasses.asdict(self)


def summarize(lane, t_end, busy_node_secs, lat_sum, lat_max, n_msgs,
              policy: Policy, pm: PowerModel, topo) -> SimResult:
    """One cell's statistics from its lane's ``LANE_SUMS`` row (from
    ``lane_sums``) and its replay's end time, busy node-seconds and
    latency accumulators: float64 arithmetic on the host."""
    tw, ts_, ts2, _, n_wake, n_hit, n_miss, n_deep = (float(x) for x in lane)
    frac = policy.state.power_frac
    frac2 = policy.deep.power_frac
    link_e = 2 * pm.port_power * (tw + frac * ts_ + frac2 * ts2)
    switch_e = float(pm.switch_power * topo.n_switches * t_end)
    node_e = float(pm.node_power_min * topo.n_nodes * t_end
                   + (pm.node_power_max - pm.node_power_min) * busy_node_secs)
    total_t = tw + ts_ + ts2
    return SimResult(
        makespan=float(t_end),
        mean_latency=float(lat_sum / max(n_msgs, 1)),
        max_latency=float(lat_max),
        n_messages=int(n_msgs),
        link_energy=link_e,
        switch_energy=switch_e,
        node_energy=node_e,
        total_energy=link_e + switch_e + node_e,
        asleep_frac=(ts_ + ts2) / max(total_t, 1e-30),
        deep_frac=ts2 / max(total_t, 1e-30),
        n_wake_transitions=int(n_wake),
        hits=int(n_hit),
        misses=int(n_miss),
        deep_misses=int(n_deep),
    )


# ---------------------------------------------------------------------------
# Phase-structured trace replay (execution-time semantics)
# ---------------------------------------------------------------------------


def _pad_msgs(links, dirs, nhops, t_inj, nbytes, bucket_min=64):
    """Serial front-end of the shared padder: host arrays in, device
    ``(links, dirs, nhops, t_inj, nbytes, valid)`` tuple out."""
    out = pad_message_table(links, dirs, nhops, t_inj, nbytes,
                            bucket_min=bucket_min)
    return tuple(jnp.asarray(a) for a in out)


def simulate_trace(trace, topo, policy: Policy, pm: PowerModel | None = None,
                   collect_events=False):
    """Replay a Trace (see repro.traffic.trace) under a policy.

    Runs on the compiled plan pipeline: ``repro.traffic.plan.compile_plan``
    (cached per (trace, topo)) + the ``repro.core.replay`` scan executor,
    as the B=1 case of the batched sweep engine.  Results match the host
    step-loop reference (``simulate_trace_reference``) to float64
    tolerance — enforced by ``tests/test_plan.py``.

    Returns (SimResult, events) — events is a list of per-step host arrays
    (link, t_start, t_end) when collect_events, else None.
    """
    from repro.core import replay  # late: replay imports us
    pm = pm or PowerModel()
    plan = compile_plan(trace, topo)
    nets, t_end, lat_sum, lat_max, seg_events = replay.replay_plan(
        plan, [policy], pm, collect_events)
    lane = lane_sums(nets, t_end, [policy], topo.n_links)[0]
    res = summarize(lane, float(t_end[0]), plan.busy, float(lat_sum[0]),
                    float(lat_max[0]), plan.n_msgs, policy, pm, topo)
    events = (replay.events_to_host(plan, seg_events) if collect_events
              else None)
    return res, events


def simulate_trace_reference(trace, topo, policy: Policy,
                             pm: PowerModel | None = None,
                             collect_events=False):
    """Host step-loop replay — the semantic oracle for the compiled path.

    One ``sim_chunk`` dispatch per trace step with host-side injection
    sorting, route lookup and ``ready``-clock bookkeeping.  Slower than
    ``simulate_trace`` (per-step host<->device ping-pong) but with no plan
    compilation: the equivalence suite replays both and compares.
    """
    pm = pm or PowerModel()
    net = init_net(topo.n_links, policy)
    ready = np.zeros(topo.n_nodes, np.float64)
    busy = 0.0
    lat_sum, lat_max, n_msgs = 0.0, 0.0, 0
    all_events = [] if collect_events else None

    for step in trace.steps:
        if step.compute_nodes is not None and len(step.compute_nodes):
            ready[step.compute_nodes] += step.compute_secs
            busy += float(step.compute_secs.sum())
        if step.msgs is not None and len(step.msgs):
            src = step.msgs[:, 0]
            dst = step.msgs[:, 1]
            nbytes = step.msgs[:, 2].astype(np.float64)
            t_inj = ready[src]
            order = np.argsort(t_inj, kind="stable")
            src, dst, nbytes, t_inj = (src[order], dst[order],
                                       nbytes[order], t_inj[order])
            links, dirs, nhops = topo.routes(src, dst)
            msgs = _pad_msgs(links, dirs, nhops, t_inj, nbytes)
            net, out = sim_chunk(net, msgs, policy, pm, topo.n_links,
                                 collect_events)
            delivery = np.asarray(out[0])[: len(src)]
            lat = np.asarray(out[1])[: len(src)]
            np.maximum.at(ready, dst, delivery)
            lat_sum += float(lat.sum())
            lat_max = max(lat_max, float(lat.max(initial=0.0)))
            n_msgs += len(src)
            if collect_events:
                lp, ts_, te_, act = (np.asarray(x) for x in out[2])
                m = act[: len(src)].astype(bool)
                all_events.append((lp[: len(src)][m], ts_[: len(src)][m],
                                   te_[: len(src)][m]))
        if step.barrier:
            nodes = trace.nodes
            ready[nodes] = ready[nodes].max()

    t_end = float(ready[trace.nodes].max()) if len(trace.nodes) else 0.0
    res = summarize(lane_sums(net, t_end, [policy], topo.n_links), t_end,
                    busy, lat_sum, lat_max, n_msgs, policy, pm, topo)
    return res, all_events


def unused_key(mapping: dict, base: str = "__baseline__") -> str:
    """A key not present in ``mapping`` (prefixing underscores as needed) —
    lets a hidden baseline lane ride in a user-named policy grid."""
    while base in mapping:
        base = "_" + base
    return base


def relative_rows(base: SimResult, results: dict,
                  baseline: str = "baseline") -> dict:
    """The §4 table protocol: each result as a dict row with overhead /
    saving percentages vs ``base`` (which leads the rows, reporting
    zeros).  Degenerate baselines (empty traces) report 0 instead of
    dividing by zero.  Shared by ``compare_policies`` and the scenario
    suite (``repro.scenarios.suite``)."""
    out = {baseline: dict(base.as_dict(), exec_overhead_pct=0.0,
                          latency_overhead_pct=0.0, energy_saved_pct=0.0,
                          link_energy_saved_pct=0.0)}
    for name, r in results.items():
        out[name] = dict(
            r.as_dict(),
            exec_overhead_pct=100 * (r.makespan / base.makespan - 1)
            if base.makespan else 0.0,
            latency_overhead_pct=100 * (r.mean_latency / base.mean_latency - 1)
            if base.mean_latency else 0.0,
            energy_saved_pct=100 * (1 - r.total_energy / base.total_energy)
            if base.total_energy else 0.0,
            link_energy_saved_pct=100 * (1 - r.link_energy / base.link_energy)
            if base.link_energy else 0.0,
        )
    return out


def compare_policies(trace, topo, policies: dict, pm: PowerModel | None = None,
                     baseline: str = "baseline",
                     max_group: int | None = None):
    """Run a trace under several policies; report overheads vs the baseline
    (always-on) run — the paper's evaluation protocol (§4).

    Runs on the batched sweep engine (``repro.core.sweep``): policies
    sharing static structure replay the trace together in one compiled
    scan per chunk instead of once each.
    """
    from repro.core.sweep import sweep_policies  # late: sweep imports us
    pm = pm or PowerModel()
    base_key = unused_key(policies)
    results = sweep_policies(trace, topo,
                             {base_key: Policy(kind="none"), **policies},
                             pm, max_group=max_group)
    base = results.pop(base_key)
    return relative_rows(base, results, baseline)
