"""Kernel microbenchmarks: the decoupled evaluator's Pallas kernels,
compiled for the TPU, vs the pure-jnp oracle, on paper-scale port counts
(20800 directed port-ends), plus oracle-parity checks.  Off the TPU the
kernel rows fail: nothing falls back to interpret mode."""
from __future__ import annotations

import numpy as np

from benchmarks.common import PM, Row, timed
from repro.core.eee import DEEP_SLEEP
from repro.kernels import ops


def run(scale: str = "small"):
    rng = np.random.default_rng(0)
    P = 20800 if scale == "paper" else 2048
    E, B = 512, 200
    gaps = rng.uniform(0, 2e-3, (E, P)).astype(np.float32)
    durs = rng.uniform(0, 1e-4, (E, P)).astype(np.float32)
    tpdt = rng.uniform(0, 1e-3, (P,)).astype(np.float32)
    tail = rng.uniform(0, 1.0, (P,)).astype(np.float32)
    counts = rng.integers(0, 20, (P, B)).astype(np.float32)
    centers = ((np.arange(B) + 0.5) * 1e-5).astype(np.float32)
    sums = counts * centers[None]
    N = rng.uniform(0, 50, (P,)).astype(np.float32)
    total = counts.sum(1)

    rows = []

    def bench(name, fn, *args, check=None, **kw):
        out, _ = timed(fn, *args, **kw)          # compile
        outs, us = [], []
        for _ in range(3):
            out, u = timed(fn, *args, **kw)
            us.append(u)
        parity = ""
        if check is not None:
            ref = fn(*args, **kw, use_ref=True)
            err = check(out, ref)
            parity = f" max_err={err:.2e}"
        rows.append(Row(f"kernels/{name}", float(np.median(us)),
                        f"P={P}{parity}"))
        return out

    def arr_err(a, b):
        return float(np.max(np.abs(np.asarray(a) - np.asarray(b))))

    def dict_err(a, b):
        return max(arr_err(a[k], b[k]) for k in a)

    def pair_err(a, b):
        return max(arr_err(a[0], b[0]), arr_err(a[1], b[1]))

    bench("tpdt_select", lambda *a, **k: ops.tpdt_select_op(*a, **k),
          counts, sums, N, total, centers,
          max_tpdt=10e-3, tpdt_init=1e-3, check=arr_err)
    bench("hist_update", lambda *a, **k: ops.hist_update_op(*a, **k),
          gaps, n_bins=B, bin_width=10e-6, check=pair_err)
    bench("port_energy", lambda *a, **k: ops.port_energy_op(*a, **k),
          gaps, durs, tpdt, tail, t_w=DEEP_SLEEP.t_w, t_s=DEEP_SLEEP.t_s,
          check=dict_err)

    # model-side kernels (reduced shapes; TPU program identical)
    q = rng.normal(size=(2, 256, 8, 64)).astype(np.float32)
    k = rng.normal(size=(2, 256, 2, 64)).astype(np.float32)
    v = rng.normal(size=(2, 256, 2, 64)).astype(np.float32)
    bench("flash_attn_fwd", lambda *a, **kw: ops.flash_attention_op(*a, **kw),
          q, k, v, causal=True, block_q=64, block_kv=64, check=arr_err)
    xs = rng.normal(size=(2, 256, 4, 32)).astype(np.float32)
    dts = rng.uniform(0.001, 0.1, (2, 256, 4)).astype(np.float32)
    Bc = rng.normal(size=(2, 256, 16)).astype(np.float32)
    Cc = rng.normal(size=(2, 256, 16)).astype(np.float32)
    A = (-rng.uniform(0.5, 4.0, 4)).astype(np.float32)
    Dp = rng.normal(size=4).astype(np.float32)
    bench("ssd_fwd", lambda *a, **kw: ops.ssd_op(*a, **kw),
          xs, dts, Bc, Cc, A, Dp, chunk=64, check=pair_err)
    return rows
