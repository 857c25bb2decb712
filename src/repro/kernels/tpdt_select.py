"""Pallas TPU kernel: PerfBound t_PDT bin selection over all ports at once.

Layout: bins on sublanes (B padded to a multiple of 8), ports on lanes
(TILE_P=128 per grid step), so every per-port quantity is a (1, TILE_P)
lane row.  The reverse cumulative sum over bins is a matmul with an
upper-triangular ones matrix — MXU-friendly, no sequential scan — and the
leftmost feasible bin is found with a float min-reduction over sublanes
and selected with a one-hot reduction.  The per-port operands N/total
travel as rows of one (8, P) array and the bin centers as a lane-broadcast
(Bp, 128) block, so every block is aligned to the (8, 128) f32 tile.

VMEM per block: counts/sums (Bp x 128 f32) + the (Bp x Bp) triangular
matrix: 2*256*128*4 + 256*256*4 = 512 KB at B=256.
"""
from __future__ import annotations

import functools

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
SUBLANE = 8
ROWS = 8
N_ROW, TOTAL_ROW = 0, 1


def _kernel(counts_ref, sums_ref, port_ref, centers_ref, tpdt_ref, *,
            n_bins, max_tpdt, tpdt_init):
    # every constant is an explicit f32 scalar: under x64 a Python float
    # would enter the kernel as f64, which Mosaic cannot lower
    f32 = jnp.float32
    c = counts_ref[...]                       # (Bp, TP)
    s = sums_ref[...]
    N = port_ref[N_ROW:N_ROW + 1, :]          # (1, TP)
    total = port_ref[TOTAL_ROW:TOTAL_ROW + 1, :]
    centers = centers_ref[...]                # (Bp, TP), lane-broadcast
    Bp = c.shape[0]

    # reverse cumsum via triangular matmul: rcum[j, :] = sum_{i>=j} c[i, :]
    row = lax.broadcasted_iota(jnp.int32, (Bp, Bp), 0)
    col = lax.broadcasted_iota(jnp.int32, (Bp, Bp), 1)
    tri = (col >= row).astype(jnp.float32)
    rcum = jnp.dot(tri, c, preferred_element_type=jnp.float32,
                   precision=lax.Precision.HIGHEST)

    binf = lax.broadcasted_iota(jnp.int32, c.shape, 0).astype(jnp.float32)
    feas = (rcum <= N) & (binf < f32(n_bins))
    j = jnp.min(jnp.where(feas, binf, f32(Bp)), axis=0, keepdims=True)
    found = j < f32(Bp)
    oh = (binf == j).astype(jnp.float32)
    cj = jnp.sum(c * oh, axis=0, keepdims=True)
    sj = jnp.sum(s * oh, axis=0, keepdims=True)
    ctr = jnp.sum(centers * oh, axis=0, keepdims=True)
    mean = jnp.where(cj > f32(0), sj / jnp.maximum(cj, f32(1e-30)), ctr)
    t = jnp.where(found, mean, f32(max_tpdt))
    t = jnp.where(total > f32(0), t, f32(tpdt_init))
    tpdt_ref[...] = jnp.broadcast_to(t, tpdt_ref.shape)


def tpdt_select_pallas(counts, sums, N, total, centers, *, max_tpdt,
                       tpdt_init, interpret=False):
    """counts/sums: (P, B) f32; N/total: (P,); centers: (B,).
    Returns the selected t_PDT, (P,) f32."""
    P, B = counts.shape
    Pp = pl.cdiv(P, TILE_P) * TILE_P
    Bp = pl.cdiv(B, SUBLANE) * SUBLANE

    def binsT(x):
        return jnp.zeros((Bp, Pp), jnp.float32).at[:B, :P].set(
            x.astype(jnp.float32).T)

    port = jnp.zeros((ROWS, Pp), jnp.float32)
    port = port.at[N_ROW, :P].set(N.astype(jnp.float32))
    port = port.at[TOTAL_ROW, :P].set(total.astype(jnp.float32))
    ctr = jnp.zeros((Bp, TILE_P), jnp.float32).at[:B].set(
        jnp.broadcast_to(centers.astype(jnp.float32)[:, None], (B, TILE_P)))

    out = pl.pallas_call(
        functools.partial(_kernel, n_bins=B, max_tpdt=float(max_tpdt),
                          tpdt_init=float(tpdt_init)),
        grid=(Pp // TILE_P,),
        in_specs=[
            pl.BlockSpec((Bp, TILE_P), lambda i: (_ZERO, i)),
            pl.BlockSpec((Bp, TILE_P), lambda i: (_ZERO, i)),
            pl.BlockSpec((ROWS, TILE_P), lambda i: (_ZERO, i)),
            pl.BlockSpec((Bp, TILE_P), lambda i: (_ZERO, _ZERO)),
        ],
        out_specs=pl.BlockSpec((ROWS, TILE_P), lambda i: (_ZERO, i)),
        out_shape=jax.ShapeDtypeStruct((ROWS, Pp), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="tpdt_select",
    )(binsT(counts), binsT(sums), port, ctr)
    return out[0, :P]
