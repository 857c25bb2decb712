"""Pallas TPU kernel: batched inactivity-histogram build.

Layout: histogram bins on sublanes, ports on lanes, so the kernel builds
the transposed (B, P) histogram.  Each event row of the (E, P) input is a
(1, TILE_P) lane vector; its bin ids compare against a (Bp, 1)-broadcast
sublane iota to give the (Bp, TILE_P) one-hot update directly, with no
relayout.  The grid tiles ports (TILE_P=128 lanes, "parallel") and events
(TILE_E rows, "arbitrary"); a fori loop walks the rows of each event block
and accumulates into the resident output blocks.

VMEM: gaps block (512 x 128 f32, double buffered) + two (Bp x 128)
accumulators: 2*512*128*4 + 2*256*128*4 = 0.8 MB at B=256.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE_P = 128
# block-index constant: a Python 0 would be int64 under x64, which
# Mosaic's index maps cannot return
_ZERO = np.int32(0)
TILE_E = 512
SUBLANE = 8


def _kernel(gaps_ref, counts_ref, sums_ref, *, n_bins, bin_width, log_bins,
            log_min, log_max):
    # every constant is an explicit 32-bit scalar: under x64 a Python
    # scalar would enter the kernel as f64/i64, which Mosaic cannot lower
    f32, i32 = jnp.float32, jnp.int32
    te = gaps_ref.shape[0]
    bins = lax.broadcasted_iota(jnp.int32, counts_ref.shape, 0)
    lo, hi = i32(0), i32(n_bins - 1)

    @pl.when(pl.program_id(1) == 0)
    def _():
        counts_ref[...] = jnp.zeros_like(counts_ref)
        sums_ref[...] = jnp.zeros_like(sums_ref)

    def body(r, carry):
        g = gaps_ref[pl.ds(r, 1), :]                # (1, TILE_P)
        valid = g > f32(0)
        if log_bins:
            lmin, lmax = math.log(log_min), math.log(log_max)
            x = (jnp.log(jnp.maximum(g, f32(log_min))) - f32(lmin)) \
                / f32(lmax - lmin)
            b = (x * f32(n_bins)).astype(jnp.int32)
        else:
            b = (g / f32(bin_width)).astype(jnp.int32)
        b = jnp.minimum(jnp.maximum(b, lo), hi)
        oh = ((bins == b) & valid).astype(jnp.float32)
        counts_ref[...] += oh
        sums_ref[...] += oh * jnp.where(valid, g, f32(0))
        return carry

    lax.fori_loop(i32(0), i32(te), body, i32(0))


def hist_update_pallas(gaps, *, n_bins, bin_width, log_bins=False,
                       log_min=1e-7, log_max=10.0, interpret=False):
    """gaps: (E, P) f32.  Returns (counts (P,B), sums (P,B))."""
    E, P = gaps.shape
    Pp = pl.cdiv(P, TILE_P) * TILE_P
    Bp = pl.cdiv(n_bins, SUBLANE) * SUBLANE
    te = min(TILE_E, pl.cdiv(E, SUBLANE) * SUBLANE)
    Ep = pl.cdiv(E, te) * te
    # padded events are gaps of 0: not valid, they add nothing
    g = jnp.zeros((Ep, Pp), jnp.float32).at[:E, :P].set(
        gaps.astype(jnp.float32))

    counts, sums = pl.pallas_call(
        functools.partial(_kernel, n_bins=n_bins, bin_width=float(bin_width),
                          log_bins=bool(log_bins), log_min=float(log_min),
                          log_max=float(log_max)),
        grid=(Pp // TILE_P, Ep // te),
        in_specs=[pl.BlockSpec((te, TILE_P), lambda i, e: (e, i))],
        out_specs=[pl.BlockSpec((Bp, TILE_P), lambda i, e: (_ZERO, i)),
                   pl.BlockSpec((Bp, TILE_P), lambda i, e: (_ZERO, i))],
        out_shape=[jax.ShapeDtypeStruct((Bp, Pp), jnp.float32),
                   jax.ShapeDtypeStruct((Bp, Pp), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="hist_update",
    )(g)
    return counts[:n_bins, :P].T, sums[:n_bins, :P].T
