"""Jit'd public wrappers for the Pallas kernels.

The decoupled evaluator's kernels (``tpdt_select``, ``hist_update``,
``port_energy``) compile to Mosaic for the TPU.  Interpret mode — the
kernel body run as traced JAX ops, validating the exact pallas_call /
BlockSpec program against the ref.py oracles — happens only where the
caller asks for it with ``interpret=True`` (the CPU test-suite); a
compiled call on any other backend raises instead of interpreting.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.hist_update import hist_update_pallas
from repro.kernels.port_energy import port_energy_pallas
from repro.kernels.tpdt_select import tpdt_select_pallas


def _require_tpu(kernel: str) -> None:
    """A compiled (not interpreted) kernel call needs a TPU backend."""
    backend = jax.default_backend()
    if backend != "tpu":
        raise RuntimeError(
            f"{kernel}: the Pallas kernel compiles for TPU only, and the "
            f"default backend is {backend!r}; pass interpret=True to run "
            f"it in Pallas interpret mode, or use_ref=True for the oracle")


@partial(jax.jit, static_argnames=("max_tpdt", "tpdt_init", "use_ref",
                                   "interpret"))
def tpdt_select_op(counts, sums, N, total, centers, *, max_tpdt, tpdt_init,
                   use_ref=False, interpret=False):
    f32 = lambda x: x.astype(jnp.float32)
    if use_ref:
        return ref.tpdt_select_ref(f32(counts), f32(sums), f32(N), f32(total),
                                   f32(centers), max_tpdt=max_tpdt,
                                   tpdt_init=tpdt_init)
    if not interpret:
        _require_tpu("tpdt_select")
    return tpdt_select_pallas(f32(counts), f32(sums), f32(N), f32(total),
                              f32(centers), max_tpdt=max_tpdt,
                              tpdt_init=tpdt_init, interpret=interpret)


@partial(jax.jit, static_argnames=("n_bins", "bin_width", "log_bins",
                                   "log_min", "log_max", "use_ref",
                                   "interpret"))
def hist_update_op(gaps, *, n_bins, bin_width, log_bins=False, log_min=1e-7,
                   log_max=10.0, use_ref=False, interpret=False):
    g = gaps.astype(jnp.float32)
    kw = dict(n_bins=n_bins, bin_width=bin_width, log_bins=log_bins,
              log_min=log_min, log_max=log_max)
    if use_ref:
        return ref.hist_update_ref(g, **kw)
    if not interpret:
        _require_tpu("hist_update")
    return hist_update_pallas(g, **kw, interpret=interpret)


@partial(jax.jit, static_argnames=("t_w", "t_s", "t_w2", "t_s2", "use_ref",
                                   "interpret"))
def port_energy_op(gaps, durs, tpdt, tail, t_dst=None, hold=None, *, t_w, t_s,
                   t_w2=0.0, t_s2=0.0, use_ref=False, interpret=False):
    """Per-port energy replay; the dual-mode row (t_w2/t_s2) engages for
    gaps past ``tpdt + max(t_dst, t_s)``.  The state-table rows are static
    (a 2-entry table), but ``t_dst`` — a continuously swept knob — is a
    TRACED scalar/(P,) operand, so a demotion-timer curve reuses one
    compiled kernel (None -> +inf -> single-state).  ``hold`` is the
    predictive hold-at-source row (precoalesce), equally traced
    (None -> 0 -> off): a hold_delay curve also reuses one kernel."""
    f32 = lambda x: x.astype(jnp.float32)
    if t_dst is None:
        t_dst = jnp.inf
    if hold is None:
        hold = 0.0
    t_dst = jnp.asarray(t_dst, jnp.float32)
    hold = jnp.asarray(hold, jnp.float32)
    kw = dict(t_w=t_w, t_s=t_s, t_w2=t_w2, t_s2=t_s2, t_dst=t_dst, hold=hold)
    if use_ref:
        return ref.port_energy_ref(f32(gaps), f32(durs), f32(tpdt), f32(tail),
                                   **kw)
    if not interpret:
        _require_tpu("port_energy")
    return port_energy_pallas(f32(gaps), f32(durs), f32(tpdt), f32(tail),
                              **kw, interpret=interpret)


def _llm_interpret() -> bool:
    """Backend-chosen interpret mode of the seed LLM stack's kernels
    (flash attention, SSD), which the simulator does not run."""
    return jax.default_backend() != "tpu"


@partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                   "block_kv", "use_ref"))
def flash_attention_op(q, k, v, *, causal=True, window=None, block_q=512,
                       block_kv=1024, use_ref=False):
    """Differentiable flash attention (custom_vjp: Pallas fwd + FA2-style
    two-pass Pallas bwd)."""
    from repro.kernels.flash_attn import flash_attention
    if use_ref:
        return ref.flash_attention_ref(q, k, v, causal=causal,
                                       window=window)
    return flash_attention(q, k, v, causal, window, block_q, block_kv,
                           _llm_interpret())


@partial(jax.jit, static_argnames=("chunk", "use_ref"))
def ssd_op(xs, dt, Bc, Cc, A, D, *, chunk=128, use_ref=False):
    """Mamba2 SSD chunked forward (fresh sequence)."""
    from repro.kernels.ssd import ssd_pallas
    if use_ref:
        return ref.ssd_ref(xs, dt, Bc, Cc, A, D, chunk=chunk)
    return ssd_pallas(xs, dt, Bc, Cc, A, D, chunk=chunk,
                      interpret=_llm_interpret())


@partial(jax.jit, static_argnames=("chunk",))
def ssd_op_vjp(xs, dt, Bc, Cc, A, D, *, chunk=128):
    """Differentiable SSD: Pallas forward + oracle-recompute backward."""
    from repro.kernels.ssd import ssd
    return ssd(xs, dt, Bc, Cc, A, D, chunk, _llm_interpret())
