"""Pallas TPU kernel: decoupled per-port EEE/PDT energy replay.

This is the TPU-native rethink of the paper's per-port state machine (see
DESIGN.md §3): all ports march through their event streams in lockstep, one
(gap, duration) pair per step, with the EEE wake/sleep bookkeeping expressed
as vector selects.  Exact for energy/hit/miss statistics given fixed arrival
times (no latency feedback); the coupled `lax.scan` simulator quantifies the
difference.

Dual-mode ladder (DESIGN.md §6): a gap past ``tpdt + t_dst`` demotes the
port to the deep row (t_w2/t_s2) — the extra down transition integrates at
wake power, the span between transitions at the row-1 floor and the
remainder at the row-2 floor.  ``t_dst = inf`` (the single-state lowering)
keeps every row-2 select on its row-1 value, so classic policies are
bit-identical to the pre-ladder kernel.

With the per-port t_PDT fixed, every event's contribution depends only on
its own (gap, duration) pair, so the replay is a reduction over events.
Layout: events on sublanes, ports on lanes.  The grid tiles ports
(TILE_P=128 lanes, "parallel") and events (TILE_E rows, "arbitrary"); each
step reduces a (TILE_E, TILE_P) block over its rows into an (8, TILE_P)
output block that stays resident across the event axis.  The four per-port
operands travel as rows of one (8, P) array, so every block is aligned to
the (8, 128) f32 tile.  VMEM: gaps+durs 2 x 512 x 128 f32, double
buffered = 1 MB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE_P = 128
# block-index constant: a Python 0 would be int64 under x64, which
# Mosaic's index maps cannot return
_ZERO = np.int32(0)
TILE_E = 512
ROWS = 8
# per-port operand rows of the (8, P) input and result rows of the output
TPDT, TDS, HOLD, TAIL = range(4)
KEYS = ("time_wake", "time_sleep", "time_sleep2", "n_wake", "hits",
        "misses", "n_deep")


def _kernel(gaps_ref, durs_ref, port_ref, out_ref, *, t_w, t_s, t_w2, t_s2):
    # every constant is an explicit f32 scalar: under x64 a Python float
    # would enter the kernel as f64, which Mosaic cannot lower
    f32 = jnp.float32
    t_w, t_s, t_w2, t_s2 = f32(t_w), f32(t_s), f32(t_w2), f32(t_s2)
    zero = f32(0)
    e = pl.program_id(1)
    port = port_ref[...]
    tpdt = port[TPDT:TPDT + 1]                      # (1, TILE_P)
    # per-port demotion timer, pre-clamped to >= t_s by the caller
    # (demotion cannot precede the first down transition)
    tds = port[TDS:TDS + 1]
    # predictive row: hold-at-source deferral granted to frames that find
    # the port asleep — the effective gap stretches by ``hold`` (0 = off)
    hold = port[HOLD:HOLD + 1]
    g = gaps_ref[...]                               # (TILE_E, TILE_P)
    d = durs_ref[...]
    act = d > zero
    asleep = act & (g >= tpdt)
    ge = g + jnp.where(asleep, hold, zero)
    deep = act & (ge >= tpdt + tds)
    wake_fast = tpdt + t_s + t_w + d
    wake_deep = tpdt + t_s + t_s2 + t_w2 + d
    wake_add = jnp.where(asleep, jnp.where(deep, wake_deep, wake_fast),
                         g + d)
    sleep_add = jnp.where(asleep,
                          jnp.where(deep, tds - t_s,
                                    jnp.maximum(ge - tpdt - t_s, zero)),
                          zero)
    sleep2_add = jnp.where(deep, jnp.maximum(ge - tpdt - tds - t_s2, zero),
                           zero)
    af = asleep.astype(jnp.float32)

    def rsum(x):
        return jnp.sum(x, axis=0, keepdims=True)

    adds = (rsum(jnp.where(act, wake_add, zero)),
            rsum(jnp.where(act, sleep_add, zero)),
            rsum(sleep2_add), rsum(af),
            rsum((act & ~asleep).astype(jnp.float32)), rsum(af),
            rsum(deep.astype(jnp.float32)))

    @pl.when(e == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    for k, v in enumerate(adds):
        out_ref[k:k + 1, :] += v

    @pl.when(e == pl.num_programs(1) - 1)
    def _():
        tail = port[TAIL:TAIL + 1]
        tail_sleeps = tail >= tpdt + t_s
        tail_deep = tail >= tpdt + tds + t_s2
        out_ref[0:1, :] += jnp.where(
            tail_sleeps, tpdt + t_s + jnp.where(tail_deep, t_s2, zero), tail)
        out_ref[1:2, :] += jnp.where(
            tail_sleeps, jnp.where(tail_deep, tds - t_s, tail - tpdt - t_s),
            zero)
        out_ref[2:3, :] += jnp.where(tail_deep, tail - tpdt - tds - t_s2,
                                     zero)


def port_energy_pallas(gaps, durs, tpdt, tail, *, t_w, t_s,
                       t_w2=0.0, t_s2=0.0, t_dst=None, hold=None,
                       interpret=False):
    """gaps/durs: (E, P) f32; tpdt/tail: (P,) f32; t_dst: scalar or (P,)
    demotion timer (traced — a timer sweep reuses ONE compiled kernel;
    None/inf = single-state).  ``hold``: scalar or (P,) hold-at-source
    deferral (the precoalesce row; traced, None/0 = off).
    Returns dict of (P,)."""
    E, P = gaps.shape
    Pp = pl.cdiv(P, TILE_P) * TILE_P
    te = min(TILE_E, pl.cdiv(E, ROWS) * ROWS)
    Ep = pl.cdiv(E, te) * te
    if t_dst is None:
        t_dst = jnp.inf
    if hold is None:
        hold = 0.0
    tds = jnp.maximum(jnp.asarray(t_dst, jnp.float32), jnp.float32(t_s))

    def padE(x):
        # padded rows carry duration 0: inactive, they add nothing
        return jnp.zeros((Ep, Pp), jnp.float32).at[:E, :P].set(
            x.astype(jnp.float32))

    # padded ports never sleep (tpdt 1e30) and never demote (tds inf)
    port = jnp.zeros((ROWS, Pp), jnp.float32)
    port = port.at[TPDT].set(1e30).at[TDS].set(jnp.inf)
    for row, v in ((TPDT, tpdt), (TDS, tds), (HOLD, hold), (TAIL, tail)):
        port = port.at[row, :P].set(
            jnp.broadcast_to(jnp.asarray(v, jnp.float32), (P,)))

    out = pl.pallas_call(
        functools.partial(_kernel, t_w=float(t_w), t_s=float(t_s),
                          t_w2=float(t_w2), t_s2=float(t_s2)),
        grid=(Pp // TILE_P, Ep // te),
        in_specs=[pl.BlockSpec((te, TILE_P), lambda i, e: (e, i)),
                  pl.BlockSpec((te, TILE_P), lambda i, e: (e, i)),
                  pl.BlockSpec((ROWS, TILE_P), lambda i, e: (_ZERO, i))],
        out_specs=pl.BlockSpec((ROWS, TILE_P), lambda i, e: (_ZERO, i)),
        out_shape=jax.ShapeDtypeStruct((ROWS, Pp), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="port_energy",
    )(padE(gaps), padE(durs), port)
    return {k: out[i, :P] for i, k in enumerate(KEYS)}
