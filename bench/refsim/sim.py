"""Frozen host step-loop replay: the benchmark's plain reference.

A copy of the simulator's semantic oracle (``simulate_trace_reference``)
and of the per-message step arithmetic it drives, kept with the benchmark
so that no later change to the program moves the yardstick.  Each trace
step is one ``lax.scan`` over its injection-ordered messages, with the
per-node ready clocks, barriers and injection ordering kept on the host.
None of the compiled plan, its packing, its stacking over traces or
lanes, or its executors is involved.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial, lru_cache

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from refsim import perfbound as pb
from refsim.eee import Policy, PowerModel, policy_params

BUCKET_MIN = 64


def bucket_cap(M: int, bucket_min: int = BUCKET_MIN) -> int:
    """Power-of-two capacity bucket for M messages (identical bucketing
    across the serial, batched, and plan engines keeps their recompilation
    behaviour aligned).  M <= 1 needs exactly one slot: ``max(M - 1, 0)``
    (NOT ``max(M - 1, 1)``, which silently rounded M=0/M=1 up to a 2-slot
    bucket whenever ``bucket_min`` is 1)."""
    return max(bucket_min, 1 << max(M - 1, 0).bit_length())


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



MAX_HOPS = 5


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
    dl0 = pb._initial_tpdt(policy, params)
    dl2_0 = dl0 + jnp.maximum(p["t_dst"], p["t_s"])
    net = {
        "dir_free": jnp.zeros((2 * n_links + 1,), jnp.float64),
        "last_end": jnp.zeros((P,), jnp.float64),
        "deadline": jnp.full((P,), dl0, jnp.float64),
        "deadline2": jnp.full((P,), dl2_0, jnp.float64),
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
    t_w = p["t_w"] + p["sync_overhead"]
    t_s = p["t_s"]
    # FSM row 2 (Deep Sleep): reachable only past ``deadline2``, which
    # single-state kinds pin to +inf (t_dst = inf) — every row-2 branch
    # below then selects the row-1 value, reproducing the single-state
    # arithmetic bit for bit.
    t_w2 = p["t_w2"] + p["sync_overhead"]
    t_s2 = p["t_s2"]
    coal = policy.kind == "coalesce"
    pre = policy.kind == "precoalesce"
    defer_on = coal or pre
    t_ser = nbytes / pm.link_bandwidth

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
        asleep = ta >= dl_h
        tae = ta + jnp.where(asleep, defer_h, 0.0) if defer_on else ta
        deep = tae >= dl2_h
        in_down = asleep & (tae < dl_h + t_s)
        in_down2 = deep & (tae < dl2_h + t_s2)
        pen_fast = jnp.where(in_down, dl_h + t_s - tae, 0.0) + t_w
        pen_deep = jnp.where(in_down2, dl2_h + t_s2 - tae, 0.0) + t_w2
        pen = jnp.where(asleep, jnp.where(deep, pen_deep, pen_fast), 0.0)
        return asleep, deep, in_down, in_down2, tae, pen

    # ---- unrolled 5-hop time chain (register-only) -----------------------
    t_head = t_inj
    t_avail = jnp.zeros(active.shape, jnp.float64)
    t_start = jnp.zeros(active.shape, jnp.float64)
    if defer_on:
        # pre-occupancy arrival per hop: the moment the frame reaches the
        # port's queue, BEFORE waiting for the link to free — the time the
        # coalescing-cycle join test must use (a frame queued behind the
        # waking head is serviced after the release, but it joined before)
        t_arr = jnp.zeros(active.shape, jnp.float64)
    delivery = t_inj
    for h in range(H):
        ta = jnp.maximum(t_head, free[..., h])
        _, _, _, _, tae, pen = _fsm(ta, dl[..., h], dl2[..., h],
                                    defer_amt[..., h] if defer_on else 0.0)
        ts_ = tae + pen
        te_ = ts_ + t_ser
        t_avail = t_avail.at[..., h].set(ta)
        t_start = t_start.at[..., h].set(ts_)
        if defer_on:
            t_arr = t_arr.at[..., h].set(t_head)
        t_head = jnp.where(active[..., h], ts_ + pm.switch_latency, t_head)
        delivery = jnp.where(active[..., h], te_, delivery)

    t_end = t_start + t_ser[..., None]
    asleep, deep, in_down, in_down2, tae, _ = _fsm(
        t_avail, dl, dl2, defer_amt if defer_on else 0.0)
    gap = t_avail - last
    new_last = jnp.maximum(last, t_end)

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
    wake_fast = (dl - last) + t_s + t_w + t_ser[..., None]
    wake_deep = (dl - last) + t_s + t_s2 + t_w2 + t_ser[..., None]
    wake_add = jnp.where(asleep,
                         jnp.where(deep, wake_deep, wake_fast),
                         jnp.maximum(new_last - last, 0.0))
    sleep_add = jnp.where(asleep & ~in_down,
                          jnp.where(deep, dl2 - (dl + t_s),
                                    jnp.maximum(tae - (dl + t_s), 0.0)),
                          0.0)
    sleep2_add = jnp.where(deep & ~in_down2,
                           jnp.maximum(tae - (dl2 + t_s2), 0.0), 0.0)
    a = active.astype(jnp.float64)

    out = dict(
        active=active, a=a, asleep=asleep, deep=deep, gap=gap,
        t_avail=t_avail, t_start=t_start, t_end=t_end, new_last=new_last,
        wake_add=wake_add, sleep_add=sleep_add, sleep2_add=sleep2_add,
        delivery=delivery,
        lat=jnp.where(valid & (nhops > 0), delivery - t_inj, 0.0),
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
    t_s = p["t_s"]
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
    t_avail, t_start, t_end = ns["t_avail"], ns["t_start"], ns["t_end"]
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
    net["dir_free"] = net["dir_free"].at[dp].add(
        jnp.maximum(t_end - g["free"], 0.0) * a)
    net["last_end"] = net["last_end"].at[lp].add((new_last - g["last"]) * a)

    # ---- predictors --------------------------------------------------------
    H = links.shape[-1]
    pred = net["pred"]
    if policy.adaptive or policy.record_hist:
        pred = pb.record_gaps(pred, lp, gap, t_avail, active, policy, p)
        pred = pb.record_hops(pred, lp, nhops[..., None] - jnp.arange(H),
                              active, policy)
    if policy.kind == "perfbound_correct":
        ratio = gap / jnp.maximum(tpdt_prev, 1e-12)
        pred = pb.record_outcomes(pred, lp, asleep, ratio, active, policy)
    if policy.adaptive:
        if policy.kind == "perfbound_dual":
            new_tpdt, new_tdst = pb.compute_tpdt_tdst(
                pred, lp, t_end, p["t_w"], policy, p)
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
            new_tpdt = pb.compute_tpdt(pred, lp, t_end, p["t_w"], policy, p)
        pred = dict(pred, tpdt=pred["tpdt"].at[lp].set(
            jnp.where(active, new_tpdt, pred["tpdt"][lp])))
    net["pred"] = pred

    # deadline = end of PDT countdown after the latest transmission;
    # deadline2 = the demotion point a (clamped) t_dst further out
    tpdt_now = net["pred"]["tpdt"][lp]
    new_dl = jnp.where(active, new_last + tpdt_now, dl)
    net["deadline"] = net["deadline"].at[lp].add(new_dl - dl)
    tdst_now = net["pred"]["t_dst"][lp] \
        if policy.kind in ("perfbound_dual", "predict") else p["t_dst"]
    new_dl2 = jnp.where(active, new_dl + jnp.maximum(tdst_now, t_s), dl2)
    # masked SET, not scatter-add: adaptive t_dst legitimately swings
    # between +inf ("never demote") and finite, and inf - inf through an
    # add would latch the row at NaN, silently disabling demotion forever
    net["deadline2"] = net["deadline2"].at[lp].set(new_dl2)

    events = (lp, t_start, t_end, active)
    return net, (ns["delivery"], ns["lat"], events)


@lru_cache(maxsize=None)
def _compiled_chunk(policy: Policy, pm: PowerModel, n_links: int,
                    collect_events: bool):
    @partial(jax.jit, donate_argnums=(0,))
    def run(net, msgs, params):
        def step(net, m):
            net, (d, lat, ev) = _message_step(net, m, policy, pm, n_links,
                                              params=params)
            out = (d, lat, ev) if collect_events else (d, lat)
            return net, out
        return lax.scan(step, net, msgs)
    return run


def sim_chunk(net, msgs, policy, pm, n_links, collect_events=False):
    """msgs: tuple of arrays (links (M,5), dirs, nhops, t_inj, bytes, valid).

    The policy's numerics enter as traced operands, as in the compiled
    replay: baked in as constants, XLA would rewrite ``gap / bin_width``
    into a multiply by the rounded reciprocal and bin a gap on a bin edge
    one bin lower than the replay does."""
    params = {k: jnp.float64(v) for k, v in policy_params(policy).items()}
    return _compiled_chunk(policy, pm, n_links, collect_events)(
        net, msgs, params)


# ---------------------------------------------------------------------------
# Close-out + energy summary
# ---------------------------------------------------------------------------


def close_out(net, t_end_sim, policy: Policy, n_links: int):
    """Integrate every link's tail (last transmission .. end of sim) at the
    FSM row it ends in: awake, row-1 sleep past ``deadline``, row-2 sleep
    past ``deadline2`` (never reached by single-state kinds).  Returns
    (time_wake, time_sleep, time_sleep2)."""
    st, st2 = policy.state, policy.deep
    # jnp inputs throughout: the multi-trace readback hands numpy views in,
    # and raw numpy would warn on the (masked-away) inf-inf deep spans of
    # never-woken links
    last = jnp.asarray(net["last_end"][:n_links])
    dl = jnp.asarray(net["deadline"][:n_links])
    dl2 = jnp.asarray(net["deadline2"][:n_links])
    t_end_sim = jnp.maximum(t_end_sim, last.max())
    sleeps = dl + st.t_s < t_end_sim
    deeps = dl2 + st2.t_s < t_end_sim
    # elapsed part of the second down transition (wake power, like every
    # transition): full t_s2 once demoted, partial if the sim ends
    # mid-transition, 0 for single-state rows (dl2 = +inf)
    down2 = jnp.clip(t_end_sim - dl2, 0.0, st2.t_s)
    wake_extra = jnp.where(
        sleeps, (dl - last) + st.t_s + down2, t_end_sim - last)
    sleep_extra = jnp.where(
        sleeps, jnp.where(deeps, dl2 - (dl + st.t_s),
                          jnp.minimum(t_end_sim, dl2) - dl - st.t_s), 0.0)
    sleep2_extra = jnp.where(deeps, t_end_sim - dl2 - st2.t_s, 0.0)
    return (net["time_wake"][:n_links] + jnp.maximum(wake_extra, 0.0),
            net["time_sleep"][:n_links] + jnp.maximum(sleep_extra, 0.0),
            net["time_sleep2"][:n_links] + jnp.maximum(sleep2_extra, 0.0))


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


def summarize(net, t_end, busy_node_secs, lat_sum, lat_max, n_msgs,
              policy: Policy, pm: PowerModel, topo) -> SimResult:
    tw, ts_, ts2 = close_out(net, t_end, policy, topo.n_links)
    frac = policy.state.power_frac
    frac2 = policy.deep.power_frac
    link_e = float(2 * pm.port_power
                   * (tw.sum() + frac * ts_.sum() + frac2 * ts2.sum()))
    switch_e = float(pm.switch_power * topo.n_switches * t_end)
    node_e = float(pm.node_power_min * topo.n_nodes * t_end
                   + (pm.node_power_max - pm.node_power_min) * busy_node_secs)
    total_t = tw.sum() + ts_.sum() + ts2.sum()
    return SimResult(
        makespan=float(t_end),
        mean_latency=float(lat_sum / max(n_msgs, 1)),
        max_latency=float(lat_max),
        n_messages=int(n_msgs),
        link_energy=link_e,
        switch_energy=switch_e,
        node_energy=node_e,
        total_energy=link_e + switch_e + node_e,
        asleep_frac=float((ts_.sum() + ts2.sum())
                          / jnp.maximum(total_t, 1e-30)),
        deep_frac=float(ts2.sum() / jnp.maximum(total_t, 1e-30)),
        n_wake_transitions=int(net["n_wake"][:topo.n_links].sum()),
        hits=int(net["n_hit"][:topo.n_links].sum()),
        misses=int(net["n_miss"][:topo.n_links].sum()),
        deep_misses=int(net["n_deep"][:topo.n_links].sum()),
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
    res = summarize(net, t_end, busy, lat_sum, lat_max, n_msgs,
                    policy, pm, topo)
    return res, all_events


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
