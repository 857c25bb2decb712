"""PerfBound and PerfBoundCorrect predictor state + math (paper §2.5, §3.4).

All state lives in dense per-link arrays so the whole network's predictors
update in a few scatters per simulated message.  The same functions serve as
the pure-jnp oracle for the Pallas kernels (``repro.kernels.ref`` re-exports).

Paper mapping
-------------
* inactivity histogram: ``counts``/``sums`` (B bins; per-bin value sums so
  t_PDT = *mean* of the selected bin, as the paper specifies).
* three management modes (§3.2/§4): keep_all, self_clear (reset every
  ``hist_clear_n`` samples), circular (ring of the last ``ring_n`` samples
  with O(1) add/evict).
* hop-distance correction: per-link histogram of remaining-hops of forwarded
  packets; ``l = bound * sum_i p_i / h_i`` (Eq. 1).
* degradation budget: ``N = l * X / t_w`` with X = wall-time covered by the
  current histogram window.
* PerfBoundCorrect (§3.4): ``n_r``-slot shift register of hit/miss outcomes +
  slot-aligned log-ratio store; ``cf = miss% * geomean(ratios)``;
  ``t_PDT' = min(t_PDT * (1 + cf), max_tpdt)`` (interpretation notes in
  DESIGN.md §4).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import ieee
from repro.core.eee import policy_params

MAXH = 7  # hop-count histogram rows 0..6 (Megafly max 5, fat-tree 6)


def _params(policy, params):
    """Numeric parameter vector: the policy's own scalars by default, or a
    caller-supplied dict (possibly of traced per-lane values) for the
    batched sweep.  Static structure always comes from ``policy``."""
    return policy_params(policy) if params is None else params


def _log(x):
    # python floats keep the exact libm constant-folding of the serial path
    return math.log(x) if isinstance(x, (int, float)) else jnp.log(x)


# ---------------------------------------------------------------------------
# Binning
# ---------------------------------------------------------------------------


def bin_index(gap, policy, params=None):
    """gap (seconds) -> bin id in [0, B).

    A gap given as its float64 bit pattern (``ieee.ExactClock``) takes on
    linear bins the bin of IEEE float64 division, counted by comparison
    against the bin edges the host computes (``ieee.quotient_edges``):
    the TPU's emulated division can round a gap on an edge into the
    next bin."""
    p = _params(policy, params)
    B = policy.hist_bins
    if jnp.result_type(gap) == jnp.uint64:
        if policy.hist_log_bins:
            return bin_index(ieee.value(gap), policy, p)
        edges = p[ieee.BITS + "hist_edges"]
        return jnp.sum(ieee.key(gap)[..., None] >= ieee.key(edges),
                       axis=-1, dtype=jnp.int32)
    if policy.hist_log_bins:
        lo, hi = _log(p["hist_log_min"]), _log(p["hist_log_max"])
        x = (jnp.log(jnp.maximum(gap, p["hist_log_min"])) - lo) / (hi - lo)
        return jnp.clip((x * B).astype(jnp.int32), 0, B - 1)
    return jnp.clip((gap / p["hist_bin_width"]).astype(jnp.int32), 0, B - 1)


def bin_centers(policy, params=None):
    p = _params(policy, params)
    B = policy.hist_bins
    if policy.hist_log_bins:
        if isinstance(p["hist_log_min"], (int, float)):
            lo, hi = math.log(p["hist_log_min"]), math.log(p["hist_log_max"])
            edges = np.exp(np.linspace(lo, hi, B + 1))
            return jnp.asarray(np.sqrt(edges[:-1] * edges[1:]))
        lo, hi = jnp.log(p["hist_log_min"]), jnp.log(p["hist_log_max"])
        edges = jnp.exp(lo + (hi - lo) * jnp.arange(B + 1) / B)
        return jnp.sqrt(edges[:-1] * edges[1:])
    return (jnp.arange(B) + 0.5) * p["hist_bin_width"]


# ---------------------------------------------------------------------------
# State
# ---------------------------------------------------------------------------


def init_state(n_links, policy, params=None):
    """Predictor state for ``n_links`` (+dummy) rows.

    Non-adaptive kinds without ``record_hist`` carry ONLY the ``tpdt``
    vector — the histogram/hop arrays are dead state for them, and at
    batched-sweep scale (B lanes x P links x hist_bins f64) they dominate
    device memory.
    """
    P, B = n_links, policy.hist_bins
    st = {
        "tpdt": jnp.full((P,), _initial_tpdt(policy, params), jnp.float64),
    }
    if not (policy.adaptive or policy.record_hist):
        return st
    st.update(
        counts=jnp.zeros((P, B), jnp.float64),
        sums=jnp.zeros((P, B), jnp.float64),
        total=jnp.zeros((P,), jnp.int64),
        win_start=jnp.zeros((P,), jnp.float64),
        hops=jnp.zeros((P, MAXH), jnp.int64),
    )
    if policy.kind in ("perfbound_dual", "predict"):
        p = _params(policy, params)
        st["t_dst"] = jnp.full((P,), p["t_dst"], jnp.float64)
    if policy.kind == "predict":
        st["ewma"] = jnp.zeros((P,), jnp.float64)
    if policy.hist_mode == "circular":
        R = policy.ring_n
        st["ring_bin"] = jnp.full((P, R), -1, jnp.int32)
        st["ring_val"] = jnp.zeros((P, R), jnp.float64)
        st["ring_time"] = jnp.zeros((P, R), jnp.float64)
        st["ring_head"] = jnp.zeros((P,), jnp.int32)
        st["ring_fill"] = jnp.zeros((P,), jnp.int32)
    if policy.kind == "perfbound_correct":
        st["reg"] = jnp.zeros((P,), jnp.uint32)
        st["ratio_log"] = jnp.zeros((P, policy.n_r), jnp.float64)
        st["reg_head"] = jnp.zeros((P,), jnp.int32)
        st["n_seen"] = jnp.zeros((P,), jnp.int32)
    return st


def initial_tpdt_param(policy):
    """The parameter that arms every port's first t_PDT (``None``: the
    ports never sleep)."""
    if policy.kind == "none":
        return None
    if policy.kind in ("fixed", "dual", "coalesce", "precoalesce", "predict"):
        # predict starts dual-like: the forecaster takes over per port as
        # soon as the first gap lands in its histogram
        return "t_pdt"
    return "tpdt_init"


def _initial_tpdt(policy, params=None):
    name = initial_tpdt_param(policy)
    return jnp.inf if name is None else _params(policy, params)[name]


# ---------------------------------------------------------------------------
# Updates (batched over K link slots; links within a batch must be distinct,
# which minimal routing guarantees for the hops of one message — and which
# the wavefront executor's link-disjoint waves extend to the (m, H) slots
# of a whole wave of messages at once)
# ---------------------------------------------------------------------------


def take_rows(x, lp):
    """``x[lp]``: the rows of a per-link table that one message's hop
    rows ``lp`` name, read as one dynamic slice a row.

    On the TPU a gather of whole rows asks for a tiling in which each row
    is contiguous, and XLA re-lays the whole table out for it, the
    (links x bins) histograms included, on every message; a dynamic slice
    reads the row's tiles where they lie.  More rows than a route has
    hops (a wave's, or every link's) stay one gather.  Either way the
    values are the table's."""
    if lp.ndim != 1 or lp.shape[0] > MAXH:
        return x[lp]
    return _row_slices(x, lp)


@jax.jit
def _row_slices(x, lp):
    """The slices of ``take_rows``, jitted so that a replay program traces
    and batches them once per table shape, not at each of a message's
    reads; XLA inlines the call, so the compiled program is the same."""
    return jnp.stack([lax.dynamic_index_in_dim(x, lp[k], keepdims=False)
                      for k in range(lp.shape[0])])


def record_gaps(st, lp, gap, t_now, active, policy, params=None,
                gap_bits=None):
    """Insert inactivity gaps.  lp,gap,t_now,active: (K,) or (m, H);
    ``gap_bits``, the gaps' float64 bit patterns where the replay keeps
    exact clocks, bins them exactly (``bin_index``)."""
    p = _params(policy, params)
    do = active & (gap > 0)
    b = bin_index(gap if gap_bits is None else gap_bits, policy, p)
    g = jnp.where(do, gap, 0.0)
    inc = do.astype(st["counts"].dtype)

    if policy.hist_mode == "circular":
        R = policy.ring_n
        head = st["ring_head"][lp]
        full = st["ring_fill"][lp] >= R
        old_b = st["ring_bin"][lp, head]
        old_v = st["ring_val"][lp, head]
        evict = do & full & (old_b >= 0)
        # evict oldest, insert new (O(1))
        counts = st["counts"].at[lp, old_b].add(-evict.astype(jnp.float64))
        sums = st["sums"].at[lp, old_b].add(jnp.where(evict, -old_v, 0.0))
        counts = counts.at[lp, b].add(inc)
        sums = sums.at[lp, b].add(g)
        st = dict(
            st, counts=counts, sums=sums,
            ring_bin=st["ring_bin"].at[lp, head].set(
                jnp.where(do, b, st["ring_bin"][lp, head])),
            ring_val=st["ring_val"].at[lp, head].set(
                jnp.where(do, g, old_v)),
            ring_time=st["ring_time"].at[lp, head].set(
                jnp.where(do, t_now, st["ring_time"][lp, head])),
            ring_head=st["ring_head"].at[lp].set(
                jnp.where(do, (head + 1) % R, head)),
            ring_fill=st["ring_fill"].at[lp].add(
                (do & ~full).astype(jnp.int32)),
            total=st["total"].at[lp].add(do.astype(jnp.int64)),
        )
        # X window start = timestamp of the oldest live element
        oldest = jnp.where(st["ring_fill"][lp] >= R,
                           st["ring_time"][lp, st["ring_head"][lp]],
                           st["ring_time"][lp, 0])
        st["win_start"] = st["win_start"].at[lp].set(
            jnp.where(active, oldest, st["win_start"][lp]))
        return st

    counts, sums = st["counts"], st["sums"]
    if policy.hist_decay < 1.0:
        # exponential recency bias (beyond-paper, paper §5 future work):
        # old evidence fades at ``hist_decay`` per new sample on that port
        d = jnp.where(do, p["hist_decay"], 1.0)[..., None]
        counts = counts.at[lp].multiply(d)
        sums = sums.at[lp].multiply(d)
        # the budget window X follows the effective sample horizon
        # (~1/(1-decay) samples): pull win_start toward t_now at the same
        # rate so N = l*X/t_w shrinks consistently with the history
        ws = st["win_start"][lp]
        new_ws = ws + (1 - p["hist_decay"]) * (t_now - ws)
        st = dict(st, win_start=st["win_start"].at[lp].set(
            jnp.where(do, new_ws, ws)))
    counts = counts.at[lp, b].add(inc)
    sums = sums.at[lp, b].add(g)
    total = st["total"].at[lp].add(do.astype(jnp.int64))
    st = dict(st, counts=counts, sums=sums, total=total)

    if policy.hist_mode == "self_clear":
        clear = active & (total[lp] >= p["hist_clear_n"])
        st["counts"] = st["counts"].at[lp].set(
            jnp.where(clear[..., None], 0.0, take_rows(st["counts"], lp)))
        st["sums"] = st["sums"].at[lp].set(
            jnp.where(clear[..., None], 0.0, take_rows(st["sums"], lp)))
        st["total"] = st["total"].at[lp].set(
            jnp.where(clear, 0, st["total"][lp]))
        st["win_start"] = st["win_start"].at[lp].set(
            jnp.where(clear, t_now, st["win_start"][lp]))
    return st


def record_hops(st, lp, rem_hops, active, policy):
    h = jnp.clip(rem_hops, 0, MAXH - 1)
    return dict(st, hops=st["hops"].at[lp, h].add(active.astype(jnp.int64)))


def record_outcomes(st, lp, miss, ratio, active, policy):
    """PerfBoundCorrect shift register + ratio FIFO (slot-aligned)."""
    nr = policy.n_r
    head = st["reg_head"][lp]
    bit = jnp.uint32(1) << head.astype(jnp.uint32)
    reg = st["reg"][lp]
    new_reg = jnp.where(miss, reg | bit, reg & ~bit)
    lr = jnp.where(miss, jnp.log(jnp.maximum(ratio, 1e-12)), 0.0)
    return dict(
        st,
        reg=st["reg"].at[lp].set(jnp.where(active, new_reg, reg)),
        ratio_log=st["ratio_log"].at[lp, head].set(
            jnp.where(active, lr, st["ratio_log"][lp, head])),
        reg_head=st["reg_head"].at[lp].set(
            jnp.where(active, (head + 1) % nr, head)),
        n_seen=st["n_seen"].at[lp].set(
            jnp.where(active, jnp.minimum(st["n_seen"][lp] + 1, nr),
                      st["n_seen"][lp])),
    )


# ---------------------------------------------------------------------------
# t_PDT computation (rowwise; also the kernel oracle)
# ---------------------------------------------------------------------------


def l_factor(hops, bound):
    """hops: (..., H) counts of remaining-hop distances.  Eq. 1."""
    tot = hops.sum(-1)
    h = jnp.arange(hops.shape[-1], dtype=jnp.float64).at[0].set(1.0)
    p = hops / jnp.maximum(tot, 1)[..., None]
    l = bound * (p / h).sum(-1)
    # no history yet -> most conservative correction (distance 1)
    return jnp.where(tot > 0, l, bound)


def _suffix_sum(x):
    """Suffix (tail) accumulation along the bin axis.

    On the TPU a cumsum lowers to an O(B^2) reduce-window, whose emulated
    f64 expansion takes minutes to compile per replay program; a log-depth
    associative scan compiles in seconds.  Every other backend keeps the
    cumsum, so CPU results are bit-for-bit what they were."""
    return lax.platform_dependent(
        x,
        tpu=lambda v: lax.associative_scan(lax.add, v, axis=v.ndim - 1,
                                           reverse=True),
        default=lambda v: jnp.cumsum(v[..., ::-1], axis=-1)[..., ::-1])


def tpdt_select(counts, sums, N, total, policy, params=None, ccum=None):
    """PerfBound bin selection (vectorized over leading dims).

    From the highest bin downwards accumulate counts; pick the leftmost bin
    whose tail-accumulation is <= N; t_PDT = mean of that bin.  ``ccum``
    optionally supplies a precomputed suffix count accumulation (shared
    with ``tdst_select`` in the fused perfbound_dual path).
    """
    p = _params(policy, params)
    centers = bin_centers(policy, p)
    rcum = _suffix_sum(counts) if ccum is None else ccum
    feasible = rcum <= N[..., None]
    found = feasible.any(-1)
    j = jnp.argmax(feasible, axis=-1)
    cj = jnp.take_along_axis(counts, j[..., None], -1)[..., 0]
    sj = jnp.take_along_axis(sums, j[..., None], -1)[..., 0]
    mean = jnp.where(cj > 0, sj / jnp.maximum(cj, 1e-30), centers[j])
    t = jnp.where(found, mean, p["max_tpdt"])
    # empty-histogram fallback: no samples yet (total == 0) OR no live mass
    # (total > 0 but every count zeroed, e.g. an externally invalidated
    # histogram) — bin 0 would otherwise look feasible with an empty-bin
    # "mean" of its center, a bogusly aggressive timer
    return jnp.where((total > 0) & (rcum[..., 0] > 0), t, p["tpdt_init"])


def deep_breakeven(params) -> jnp.ndarray:
    """Residual idle time beyond the demotion point that amortizes a deep
    (row-2) wake: the extra wake transition plus the second down transition
    at wake power must be repaid by the deeper power floor.

        R* = ((t_w2 - t_w) + t_s2 * (1 - frac)) / (frac - frac2)

    Degenerate ladders (frac2 >= frac, i.e. deep saves nothing) price the
    break-even at +inf — demotion never pays.
    """
    gain = params["power_frac"] - params["power_frac2"]
    cost = (params["t_w2"] - params["t_w"]) \
        + params["t_s2"] * (1.0 - params["power_frac"])
    return jnp.where(gain > 0, cost / jnp.maximum(gain, 1e-30), jnp.inf)


def tdst_select(counts, sums, tpdt, r_star, total, policy, params=None,
                ccum=None):
    """Demotion-threshold selection from the inactivity histogram.

    For each candidate bin center T the histogram's suffix mass estimates
    the conditional residual idle E[gap - T | gap >= T]; the leftmost
    (earliest-demoting) T whose residual covers the break-even ``r_star``
    wins, and the threshold converts to a timer past the sleep deadline:
    t_dst = max(T - t_pdt, 0).  No feasible bin -> +inf (never demote);
    no history yet -> the policy's initial ``t_dst``.
    """
    p = _params(policy, params)
    centers = bin_centers(policy, p)
    if ccum is None:
        ccum = _suffix_sum(counts)
    scum = _suffix_sum(sums)
    resid = scum / jnp.maximum(ccum, 1e-30) - centers
    feasible = (ccum > 0) & (resid >= r_star[..., None])
    found = feasible.any(-1)
    j = jnp.argmax(feasible, axis=-1)
    T = centers[j]
    t = jnp.where(found, jnp.maximum(T - tpdt, 0.0), jnp.inf)
    # same empty-histogram fallback as tpdt_select: a massless histogram
    # (total == 0, or invalidated counts) keeps the initial timer instead
    # of pinning demotion off at +inf
    return jnp.where((total > 0) & (ccum[..., 0] > 0), t, p["t_dst"])


def compute_tdst(st, lp, tpdt_new, policy, params=None):
    """Recalculate the per-port demotion timer for rows ``lp`` given the
    freshly selected ``tpdt_new``.  (K,) -> (K,)."""
    p = _params(policy, params)
    r_star = jnp.broadcast_to(deep_breakeven(p), lp.shape)
    return tdst_select(take_rows(st["counts"], lp),
                       take_rows(st["sums"], lp), tpdt_new, r_star,
                       st["total"][lp], policy, p)


def compute_tpdt_tdst(st, lp, t_now, t_w, policy, params=None):
    """Fused perfbound_dual update: ONE set of histogram gathers and one
    shared suffix-count accumulation feed both the t_PDT selection and the
    demotion-threshold selection — the per-message hot path would
    otherwise do both twice.  Returns (t_pdt, t_dst), each (K,)."""
    p = _params(policy, params)
    counts = take_rows(st["counts"], lp)
    sums = take_rows(st["sums"], lp)
    total = st["total"][lp]
    ccum = _suffix_sum(counts)
    X = jnp.maximum(t_now - st["win_start"][lp], 0.0)
    l = l_factor(take_rows(st["hops"], lp), p["bound"])
    N = l * X / t_w
    t = tpdt_select(counts, sums, N, total, policy, p, ccum=ccum)
    r_star = jnp.broadcast_to(deep_breakeven(p), lp.shape)
    td = tdst_select(counts, sums, t, r_star, total, policy, p, ccum=ccum)
    return t, td


def sleep_breakeven(params) -> jnp.ndarray:
    """Gap length at which entering the (row-1) sleep state at onset pays:
    the down transition at wake power plus the wake penalty must be repaid
    by the idle power floor,

        g* = t_s + (t_w + sync) / (1 - frac).
    """
    return params["t_s"] + (params["t_w"] + params["sync_overhead"]) \
        / (1.0 - params["power_frac"])


def forecast_update(st, lp, gap, active, policy, params=None):
    """``predict`` forecaster (arXiv 1503.02843 flavor): predict the NEXT
    inactivity gap per port and schedule the timers ahead of it.

    Two estimators share the histogram state ``record_gaps`` already
    maintains.  An EWMA of observed gaps (weight ``forecast_weight`` on the
    newest) tracks drifting traffic; when one histogram bin holds at least
    ``period_conf`` of the live mass — periodic BSP traffic concentrates
    its inter-burst gap in one bin — the mode bin's mean overrides the
    EWMA (the cheap periodogram: the dominant frequency of a periodic
    arrival process IS its modal gap).

    The predicted gap then prices the FSM ladder *proactively*: if it
    covers ``forecast_margin`` x the sleep break-even the port sleeps at
    onset (t_pdt -> 0), and if it also covers the demotion break-even the
    port demotes at onset (t_dst -> 0).  When the forecast does NOT clear
    a margin the timer falls back to the policy's own reactive value —
    predict degrades gracefully to ``dual`` on unpredictable traffic
    instead of holding awake, so a large ``forecast_margin`` (never
    confident) and ``forecast_weight == 0`` (forecaster off) both
    reproduce ``dual`` bit-for-bit.

    Call AFTER ``record_gaps`` (the new gap is already in the histogram).
    Returns (tpdt_new, t_dst_new, ewma_new), each (K,).
    """
    p = _params(policy, params)
    obs = active & (gap > 0)
    w = p["forecast_weight"]
    total = st["total"][lp]
    ewma_old = st["ewma"][lp]
    first = obs & (total <= 1)
    ewma_new = jnp.where(
        first, gap,
        jnp.where(obs, (1.0 - w) * ewma_old + w * gap, ewma_old))

    counts = take_rows(st["counts"], lp)
    sums = take_rows(st["sums"], lp)
    mass = counts.sum(-1)
    j = jnp.argmax(counts, axis=-1)
    cj = jnp.take_along_axis(counts, j[..., None], -1)[..., 0]
    sj = jnp.take_along_axis(sums, j[..., None], -1)[..., 0]
    mode_mean = jnp.where(cj > 0, sj / jnp.maximum(cj, 1e-30), 0.0)
    peaked = (mass > 0) & (cj >= p["period_conf"] * mass)
    ghat = jnp.where(peaked, mode_mean, ewma_new)

    pred_on = (w > 0) & (total > 0)
    b1 = sleep_breakeven(p)
    r_star = deep_breakeven(p)
    sleep_now = ghat >= p["forecast_margin"] * b1
    deep_now = ghat >= p["forecast_margin"] * (b1 + r_star)
    tpdt_new = jnp.where(pred_on & sleep_now, 0.0, p["t_pdt"])
    tdst_new = jnp.where(pred_on & deep_now, 0.0, p["t_dst"])
    return tpdt_new, tdst_new, ewma_new


def pbc_cf(reg, ratio_log, n_seen, policy):
    """Corrective factor cf = miss% * geomean(miss ratios)."""
    nr = policy.n_r
    bits = (reg[..., None] >> jnp.arange(nr, dtype=jnp.uint32)) & 1
    bits = bits.astype(jnp.float64)
    miss_cnt = bits.sum(-1)
    n = jnp.maximum(n_seen, 1)
    miss_pct = miss_cnt / n
    gmean = jnp.exp((bits * ratio_log).sum(-1) / jnp.maximum(miss_cnt, 1.0))
    return miss_pct * jnp.where(miss_cnt > 0, gmean, 1.0)


def compute_tpdt(st, lp, t_now, t_w, policy, params=None):
    """Recalculate t_PDT for link rows ``lp`` at time ``t_now``.  (K,)->(K,)."""
    p = _params(policy, params)
    counts = take_rows(st["counts"], lp)
    sums = take_rows(st["sums"], lp)
    total = st["total"][lp]
    X = jnp.maximum(t_now - st["win_start"][lp], 0.0)
    l = l_factor(take_rows(st["hops"], lp), p["bound"])
    N = l * X / t_w
    t = tpdt_select(counts, sums, N, total, policy, p)
    if policy.kind == "perfbound_correct":
        cf = pbc_cf(st["reg"][lp], take_rows(st["ratio_log"], lp),
                    st["n_seen"][lp], policy)
        if policy.cf_mode == "uplift":
            t = t * (1.0 + cf)
        else:
            t = t * jnp.maximum(cf, 1.0)
        t = jnp.minimum(t, p["max_tpdt"])
    return t


def compute_tpdt_all(st, t_now, t_w, policy, params=None):
    """Batched periodic recalculation over every link (kernel-accelerated
    variant lives in repro.kernels.ops.tpdt_select_op)."""
    P = st["counts"].shape[0]
    return compute_tpdt(st, jnp.arange(P), t_now, t_w, policy, params)
