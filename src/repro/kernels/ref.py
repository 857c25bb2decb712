"""Pure-jnp oracles for every Pallas kernel (shape-for-shape identical)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def tpdt_select_ref(counts, sums, N, total, centers, *, max_tpdt, tpdt_init):
    """PerfBound bin selection.  counts/sums: (P,B) f32; N/total: (P,).

    From the top bin downwards accumulate counts; choose the leftmost bin
    whose tail accumulation is <= N; t_PDT = mean of that bin (value sum /
    count, falling back to the bin center when empty).
    """
    rcum = jnp.cumsum(counts[:, ::-1], axis=1)[:, ::-1]
    feas = rcum <= N[:, None]
    found = feas.any(axis=1)
    j = jnp.argmax(feas, axis=1)
    oh = jax.nn.one_hot(j, counts.shape[1], dtype=counts.dtype)
    cj = (counts * oh).sum(1)
    sj = (sums * oh).sum(1)
    ctr = (centers[None, :] * oh).sum(1)
    mean = jnp.where(cj > 0, sj / jnp.maximum(cj, 1e-30), ctr)
    t = jnp.where(found, mean, max_tpdt)
    return jnp.where(total > 0, t, tpdt_init).astype(counts.dtype)


def hist_update_ref(gaps, *, n_bins, bin_width, log_bins=False,
                    log_min=1e-7, log_max=10.0):
    """Batched histogram build.  gaps: (E,P) f32 (<=0 entries ignored).
    Returns (counts (P,B), sums (P,B))."""
    E, P = gaps.shape
    valid = gaps > 0
    if log_bins:
        lo, hi = np.log(log_min), np.log(log_max)
        x = (jnp.log(jnp.maximum(gaps, log_min)) - lo) / (hi - lo)
        b = jnp.clip((x * n_bins).astype(jnp.int32), 0, n_bins - 1)
    else:
        b = jnp.clip((gaps / bin_width).astype(jnp.int32), 0, n_bins - 1)
    oh = (b[..., None] == jnp.arange(n_bins)[None, None, :]) & valid[..., None]
    counts = oh.sum(0).astype(jnp.float32)
    sums = (oh * jnp.where(valid, gaps, 0.0)[..., None]).sum(0)
    return counts, sums.astype(jnp.float32)


def port_energy_ref(gaps, durs, tpdt, tail, *, t_w, t_s,
                    t_w2=0.0, t_s2=0.0, t_dst=None, hold=None):
    """Decoupled per-port EEE/PDT replay (fixed per-port t_PDT) with the
    dual-mode sleep ladder: gaps past ``tpdt + max(t_dst, t_s)`` demote to
    the deep row (t_w2/t_s2); ``t_dst`` is a traced scalar or (P,) timer —
    None/inf is the single-state lowering.  ``hold`` is the predictive
    hold-at-source row: a frame that finds its port asleep defers by up to
    ``hold`` seconds, stretching the effective gap (None/0 = off).

    gaps/durs: (E,P) f32 — idle gap before each busy interval and its
    duration (duration 0 = padding).  tpdt/tail: (P,).
    Returns dict of (P,) arrays: time_wake, time_sleep, time_sleep2,
    n_wake, hits, misses, n_deep.
    """
    E, P = gaps.shape
    if t_dst is None:
        t_dst = jnp.inf
    if hold is None:
        hold = 0.0
    tds = jnp.maximum(jnp.asarray(t_dst, jnp.float32), jnp.float32(t_s))
    hld = jnp.asarray(hold, jnp.float32)

    def step(carry, ed):
        wake, sleep, sleep2, nw, hit, miss, nd = carry
        g, d = ed
        act = d > 0
        asleep = act & (g >= tpdt)
        ge = g + jnp.where(asleep, hld, 0.0)
        deep = act & (ge >= tpdt + tds)
        wake_add = jnp.where(
            asleep, jnp.where(deep, tpdt + t_s + t_s2 + t_w2 + d,
                              tpdt + t_s + t_w + d), g + d)
        sleep_add = jnp.where(
            asleep, jnp.where(deep, tds - t_s,
                              jnp.maximum(ge - tpdt - t_s, 0.0)), 0.0)
        sleep2_add = jnp.where(
            deep, jnp.maximum(ge - tpdt - tds - t_s2, 0.0), 0.0)
        return (wake + jnp.where(act, wake_add, 0.0),
                sleep + jnp.where(act, sleep_add, 0.0),
                sleep2 + sleep2_add,
                nw + asleep.astype(jnp.float32),
                hit + (act & ~asleep).astype(jnp.float32),
                miss + asleep.astype(jnp.float32),
                nd + deep.astype(jnp.float32)), None

    z = jnp.zeros((P,), jnp.float32)
    (wake, sleep, sleep2, nw, hit, miss, nd), _ = jax.lax.scan(
        step, (z, z, z, z, z, z, z), (gaps, durs))
    # close-out tail
    tail_sleeps = tail >= tpdt + t_s
    tail_deep = tail >= tpdt + tds + t_s2
    wake = wake + jnp.where(
        tail_sleeps, tpdt + t_s + jnp.where(tail_deep, t_s2, 0.0), tail)
    sleep = sleep + jnp.where(
        tail_sleeps, jnp.where(tail_deep, tds - t_s, tail - tpdt - t_s), 0.0)
    sleep2 = sleep2 + jnp.where(tail_deep, tail - tpdt - tds - t_s2, 0.0)
    return {"time_wake": wake, "time_sleep": sleep, "time_sleep2": sleep2,
            "n_wake": nw, "hits": hit, "misses": miss, "n_deep": nd}

