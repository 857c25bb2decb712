"""Per-kernel validation: Pallas (interpret mode, requested explicitly) vs
the ref.py oracle, swept over shapes, plus hypothesis property tests on
kernel invariants.  A compiled kernel call off the TPU must raise."""
from functools import partial

import hypothesis.extra.numpy as hnp
import hypothesis.strategies as st
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings

from repro.core import perfbound as pb
from repro.core.eee import Policy
from repro.kernels import ops, ref

# The CPU suite runs the decoupled evaluator's kernels in interpret mode,
# asked for explicitly; ``use_ref=True`` still selects the jnp oracle.
tpdt_select_op = partial(ops.tpdt_select_op, interpret=True)
hist_update_op = partial(ops.hist_update_op, interpret=True)
port_energy_op = partial(ops.port_energy_op, interpret=True)

SHAPE_SWEEP_P = [1, 3, 64, 128, 130, 257]
SHAPE_SWEEP_B = [8, 100, 128, 200, 256]


# ---------------------------------------------------------------------------
# tpdt_select
# ---------------------------------------------------------------------------


def _rand_hist(rng, P, B):
    counts = rng.integers(0, 20, (P, B)).astype(np.float32)
    # value-sums consistent with counts: mean inside the bin
    centers = (np.arange(B) + 0.5) * 1e-5
    sums = counts * centers[None, :] * rng.uniform(0.9, 1.1, (P, B))
    sums = sums.astype(np.float32)
    N = rng.uniform(0, counts.sum(1) + 5).astype(np.float32)
    total = counts.sum(1).astype(np.float32)
    return counts, sums, N, total, centers.astype(np.float32)


@pytest.mark.parametrize("P", SHAPE_SWEEP_P)
@pytest.mark.parametrize("B", [100, 200, 256])
def test_tpdt_select_matches_ref(P, B, rng):
    counts, sums, N, total, centers = _rand_hist(rng, P, B)
    kw = dict(max_tpdt=10e-3, tpdt_init=1e-3)
    got = tpdt_select_op(counts, sums, N, total, centers, **kw)
    want = ref.tpdt_select_ref(
        *(jnp.asarray(a, jnp.float32)
          for a in (counts, sums, N, total, centers)), **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=0)


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_tpdt_select_dtypes(dtype, rng):
    counts, sums, N, total, centers = _rand_hist(rng, 64, 200)
    kw = dict(max_tpdt=10e-3, tpdt_init=1e-3)
    got = tpdt_select_op(counts.astype(dtype), sums.astype(dtype),
                         N.astype(dtype), total.astype(dtype),
                         centers.astype(dtype), **kw)
    want = tpdt_select_op(counts, sums, N, total, centers, use_ref=True,
                          **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


def test_tpdt_select_empty_history():
    """Ports with no samples predict tpdt_init; infeasible ports max_tpdt."""
    B = 200
    counts = np.zeros((2, B), np.float32)
    counts[1, B - 1] = 50.0  # one huge-bin spike, N=0 -> infeasible
    sums = counts * 1.0
    centers = (np.arange(B) + 0.5).astype(np.float32)
    N = np.zeros((2,), np.float32)
    total = counts.sum(1)
    out = np.asarray(tpdt_select_op(counts, sums, N, total, centers,
                                    max_tpdt=7.0, tpdt_init=3.0))
    assert out[0] == 3.0      # no history
    assert out[1] == 7.0      # feasible nowhere (tail count 50 > N=0)


def test_tpdt_select_leftmost_feasible(rng):
    """The oracle picks the LEFTMOST bin whose tail accumulation <= N, and
    t_PDT is that bin's mean — cross-checked against a python loop."""
    counts, sums, N, total, centers = _rand_hist(rng, 32, 64)
    out = np.asarray(tpdt_select_op(counts, sums, N, total, centers,
                                    max_tpdt=99.0, tpdt_init=-1.0))
    for p in range(32):
        rcum = np.cumsum(counts[p][::-1])[::-1]
        feas = np.nonzero(rcum <= N[p])[0]
        if total[p] == 0:
            assert out[p] == -1.0
        elif len(feas) == 0:
            assert out[p] == 99.0
        else:
            j = feas[0]
            want = (sums[p, j] / counts[p, j]) if counts[p, j] > 0 \
                else centers[j]
            np.testing.assert_allclose(out[p], want, rtol=1e-5)


@settings(max_examples=30, deadline=None)
@given(st.data())
def test_tpdt_select_property(data):
    """Selected t_PDT never exceeds max_tpdt when history exists, and the
    tail count at the chosen bin respects the budget N."""
    P = data.draw(st.integers(1, 40))
    B = 64
    counts = data.draw(hnp.arrays(np.float32, (P, B),
                                  elements=st.integers(0, 9).map(float)))
    N = data.draw(hnp.arrays(
        np.float32, (P,),
        elements=st.floats(0, 512, allow_nan=False, width=32)))
    centers = (np.arange(B) + 0.5).astype(np.float32)
    sums = counts * centers[None]
    total = counts.sum(1)
    out = np.asarray(tpdt_select_op(counts, sums, N, total, centers,
                                    max_tpdt=1e6, tpdt_init=0.5))
    rcum = np.cumsum(counts[:, ::-1], 1)[:, ::-1]
    feasible = (rcum <= N[:, None]).any(1)
    has_hist = total > 0
    sel = has_hist & feasible
    # chosen bin's tail accumulation is within budget
    j = np.clip(np.round(out - 0.5).astype(int), 0, B - 1)
    assert (rcum[np.arange(P), j][sel] <= N[sel] + 1e-3).all()
    assert (out[~has_hist] == 0.5).all()
    assert (out[has_hist & ~feasible] == 1e6).all()


# ---------------------------------------------------------------------------
# hist_update
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("E,P", [(1, 1), (7, 3), (64, 128), (100, 130),
                                 (1100, 130),
                                 (513, 64)])
@pytest.mark.parametrize("log_bins", [False, True])
def test_hist_update_matches_ref(E, P, log_bins, rng):
    gaps = rng.uniform(-1e-5, 5e-3, (E, P)).astype(np.float32)
    kw = dict(n_bins=200, bin_width=10e-6, log_bins=log_bins,
              log_min=1e-7, log_max=1.0)
    gc, gs = hist_update_op(gaps, **kw)
    wc, ws = hist_update_op(gaps, use_ref=True, **kw)
    np.testing.assert_allclose(np.asarray(gc), np.asarray(wc), atol=0)
    np.testing.assert_allclose(np.asarray(gs), np.asarray(ws),
                               rtol=1e-5, atol=1e-9)


@settings(max_examples=25, deadline=None)
@given(st.data())
def test_hist_update_conservation(data):
    """Counts sum to the number of positive gaps; sums to their total."""
    E = data.draw(st.integers(1, 50))
    P = data.draw(st.integers(1, 20))
    gaps = data.draw(hnp.arrays(
        np.float32, (E, P),
        elements=st.floats(-0.0009765625, 0.0078125, allow_nan=False,
                           allow_subnormal=False, width=32)))
    counts, sums = hist_update_op(gaps, n_bins=128, bin_width=1e-4)
    valid = gaps > 0
    np.testing.assert_allclose(np.asarray(counts).sum(1), valid.sum(0))
    np.testing.assert_allclose(np.asarray(sums).sum(1),
                               np.where(valid, gaps, 0).sum(0),
                               rtol=1e-4, atol=1e-7)


def test_hist_update_agrees_with_perfbound_binning():
    """Kernel binning == the coupled simulator's record_gaps binning."""
    pol = Policy(kind="perfbound", hist_bins=50, hist_bin_width=1e-4)
    gaps = np.array([[5e-5, 1.23e-4, 4.9e-3, 1e9]], np.float32).T  # (4,1)->
    gaps = gaps.reshape(4, 1)
    counts, _ = hist_update_op(gaps, n_bins=50, bin_width=1e-4)
    want_bins = np.asarray(pb.bin_index(jnp.asarray(gaps[:, 0]), pol))
    got_nonzero = np.nonzero(np.asarray(counts)[0])[0]
    assert sorted(set(want_bins.tolist())) == sorted(got_nonzero.tolist())


# ---------------------------------------------------------------------------
# port_energy
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("E,P", [(1, 1), (16, 64), (100, 128), (257, 130),
                                 (1100, 130)])
def test_port_energy_matches_ref(E, P, rng):
    gaps = rng.uniform(0, 2e-3, (E, P)).astype(np.float32)
    durs = rng.uniform(0, 1e-4, (E, P)).astype(np.float32)
    durs[rng.random((E, P)) < 0.2] = 0.0  # padding rows
    tpdt = rng.uniform(0, 1e-3, (P,)).astype(np.float32)
    tail = rng.uniform(0, 1.0, (P,)).astype(np.float32)
    kw = dict(t_w=4.48e-6, t_s=2e-6)
    got = port_energy_op(gaps, durs, tpdt, tail, **kw)
    want = port_energy_op(gaps, durs, tpdt, tail, use_ref=True, **kw)
    for k in got:
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(want[k]),
                                   rtol=1e-5, atol=1e-8, err_msg=k)


@settings(max_examples=25, deadline=None)
@given(st.data())
def test_port_energy_conservation(data):
    """wake + sleep time equals the stream's total span (every second of
    simulated time is accounted at exactly one power level)."""
    E = data.draw(st.integers(1, 30))
    P = data.draw(st.integers(1, 8))
    gaps = data.draw(hnp.arrays(np.float32, (E, P),
                                elements=st.floats(0, 0.0078125, width=32)))
    durs = data.draw(hnp.arrays(np.float32, (E, P),
                                elements=st.floats(9.5367431640625e-07, 0.0009765625, width=32)))
    tail = data.draw(hnp.arrays(np.float32, (P,),
                                elements=st.floats(0, 0.125, width=32)))
    tpdt = data.draw(hnp.arrays(np.float32, (P,),
                                elements=st.floats(0, 0.0078125, width=32)))
    t_w, t_s = 4.48e-6, 2e-6
    out = port_energy_op(gaps, durs, tpdt, tail, t_w=t_w, t_s=t_s)
    span = gaps.sum(0) + durs.sum(0) + tail
    total = np.asarray(out["time_wake"]) + np.asarray(out["time_sleep"])
    # Every second of the stream is accounted at exactly one power level,
    # plus: each miss extends the port timeline by t_w (wake transition at
    # wake power, §2.3) and, when the packet lands mid down-transition
    # (gap < tpdt + t_s), by the unfinished down time tpdt + t_s - gap.
    miss = (durs > 0) & (gaps >= tpdt[None, :])
    ext = np.where(miss, np.maximum(tpdt[None, :] + t_s - gaps, 0.0),
                   0.0).sum(0)
    extra = np.asarray(out["n_wake"]) * t_w + ext
    np.testing.assert_allclose(total, span + extra, rtol=1e-4, atol=1e-6)
    assert (np.asarray(out["hits"]) + np.asarray(out["misses"])
            == (durs > 0).sum(0)).all()


def test_port_energy_extremes():
    """tpdt=0 sleeps at every opportunity; tpdt=inf never sleeps."""
    gaps = np.full((4, 2), 1e-3, np.float32)
    durs = np.full((4, 2), 1e-5, np.float32)
    tail = np.full((2,), 0.1, np.float32)
    always = port_energy_op(gaps, durs, np.zeros(2, np.float32), tail,
                            t_w=4.48e-6, t_s=2e-6)
    never = port_energy_op(gaps, durs,
                           np.full((2,), 1e9, np.float32), tail,
                           t_w=4.48e-6, t_s=2e-6)
    assert (np.asarray(always["n_wake"]) == 4).all()
    assert (np.asarray(never["n_wake"]) == 0).all()
    assert (np.asarray(never["time_sleep"]) == 0).all()
    span = gaps.sum(0) + durs.sum(0) + tail
    np.testing.assert_allclose(np.asarray(never["time_wake"]), span,
                               rtol=1e-5)
    assert (np.asarray(always["time_sleep"]) > 0).all()
    assert (np.asarray(always["time_wake"]) < span).all()


@pytest.mark.parametrize("E,P", [(1, 1), (16, 64), (100, 130)])
def test_port_energy_hold_matches_ref(E, P, rng):
    """The precoalesce hold-at-source row: Pallas vs ref oracle with a
    live (P,) hold operand and a dual-mode ladder engaged."""
    gaps = rng.uniform(0, 2e-3, (E, P)).astype(np.float32)
    durs = rng.uniform(0, 1e-4, (E, P)).astype(np.float32)
    durs[rng.random((E, P)) < 0.2] = 0.0
    tpdt = rng.uniform(0, 1e-3, (P,)).astype(np.float32)
    tail = rng.uniform(0, 1.0, (P,)).astype(np.float32)
    hold = rng.uniform(0, 5e-4, (P,)).astype(np.float32)
    kw = dict(t_w=4.48e-6, t_s=2e-6, t_w2=1e-4, t_s2=1e-5)
    got = port_energy_op(gaps, durs, tpdt, tail, t_dst=2e-4, hold=hold,
                         **kw)
    want = port_energy_op(gaps, durs, tpdt, tail, t_dst=2e-4, hold=hold,
                          use_ref=True, **kw)
    for k in got:
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(want[k]),
                                   rtol=1e-5, atol=1e-8, err_msg=k)


def test_port_energy_hold_zero_is_identity(rng):
    """hold=0 and hold=None lower to the SAME program and numbers: the
    traced hold operand costs nothing when the policy kind is not
    precoalesce."""
    gaps = rng.uniform(0, 2e-3, (32, 64)).astype(np.float32)
    durs = rng.uniform(1e-6, 1e-4, (32, 64)).astype(np.float32)
    tpdt = rng.uniform(0, 1e-3, (64,)).astype(np.float32)
    tail = rng.uniform(0, 1.0, (64,)).astype(np.float32)
    kw = dict(t_w=4.48e-6, t_s=2e-6, t_w2=1e-4, t_s2=1e-5, t_dst=2e-4)
    off = port_energy_op(gaps, durs, tpdt, tail, **kw)
    zero = port_energy_op(gaps, durs, tpdt, tail, hold=0.0, **kw)
    for k in off:
        np.testing.assert_array_equal(np.asarray(off[k]),
                                      np.asarray(zero[k]), err_msg=k)


def test_port_energy_hold_stretches_gap_into_deep():
    """A hold grant only applies to frames that found the port asleep, and
    stretches the effective gap across the demotion threshold: with
    hold >= t_dst an asleep-found gap demotes to the deep row."""
    t_dst = 1e-4
    gaps = np.array([[5e-5, 1.5e-4]], np.float32)   # awake-hit, asleep-miss
    durs = np.full((1, 2), 1e-5, np.float32)
    tpdt = np.full((2,), 1e-4, np.float32)
    tail = np.zeros((2,), np.float32)
    kw = dict(t_w=4.48e-6, t_s=2e-6, t_w2=1e-4, t_s2=1e-5, t_dst=t_dst)
    off = port_energy_op(gaps, durs, tpdt, tail, hold=0.0, **kw)
    on = port_energy_op(gaps, durs, tpdt, tail, hold=t_dst, **kw)
    # port 0 never slept: the hold row must not touch it
    assert np.asarray(off["n_deep"])[0] == np.asarray(on["n_deep"])[0] == 0
    np.testing.assert_array_equal(np.asarray(off["time_wake"])[0],
                                  np.asarray(on["time_wake"])[0])
    # port 1 slept; the stretched gap crosses tpdt + t_dst and demotes
    assert np.asarray(off["n_deep"])[1] == 0
    assert np.asarray(on["n_deep"])[1] == 1
    assert np.asarray(on["time_sleep2"])[1] > 0


# ---------------------------------------------------------------------------
# interpret mode is the caller's choice
# ---------------------------------------------------------------------------


def _kernel_calls():
    gaps = np.full((4, 3), 1e-4, np.float32)
    counts = np.ones((3, 16), np.float32)
    vec = np.ones((3,), np.float32)
    centers = np.arange(16, dtype=np.float32)
    return {
        "tpdt_select": lambda **k: ops.tpdt_select_op(
            counts, counts, vec, vec, centers, max_tpdt=1.0, tpdt_init=0.5,
            **k),
        "hist_update": lambda **k: ops.hist_update_op(
            gaps, n_bins=16, bin_width=1e-5, **k),
        "port_energy": lambda **k: ops.port_energy_op(
            gaps, gaps, vec, vec, t_w=4.48e-6, t_s=2e-6, **k),
    }


@pytest.mark.parametrize("kernel", ["tpdt_select", "hist_update",
                                    "port_energy"])
def test_compiled_kernel_off_tpu_raises(kernel):
    """Off the TPU a kernel call without interpret=True raises: nothing
    drops to interpret mode unasked."""
    assert jax.default_backend() != "tpu"
    call = _kernel_calls()[kernel]
    with pytest.raises(RuntimeError, match="interpret=True"):
        call()
    jax.block_until_ready(call(interpret=True))
    jax.block_until_ready(call(use_ref=True))


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B,Sq,H,Hkv,dh,causal,window", [
    (2, 128, 4, 2, 32, True, None),
    (1, 96, 4, 4, 16, True, None),      # ragged seq vs 32-blocks, MHA
    (2, 64, 8, 2, 32, False, None),     # non-causal (encoder)
    (1, 128, 4, 2, 32, True, 48),       # sliding window (gemma3-style)
    (1, 64, 8, 1, 16, True, None),      # MQA
])
def test_flash_attention_matches_ref(B, Sq, H, Hkv, dh, causal, window, rng):
    q = jnp.asarray(rng.normal(size=(B, Sq, H, dh)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, Sq, Hkv, dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, Sq, Hkv, dh)), jnp.float32)
    out = ops.flash_attention_op(q, k, v, causal=causal, window=window,
                                 block_q=32, block_kv=32)
    want = ops.flash_attention_op(q, k, v, causal=causal, window=window,
                                  use_ref=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype, rng):
    q = jnp.asarray(rng.normal(size=(1, 64, 4, 32)), dtype)
    k = jnp.asarray(rng.normal(size=(1, 64, 2, 32)), dtype)
    v = jnp.asarray(rng.normal(size=(1, 64, 2, 32)), dtype)
    out = ops.flash_attention_op(q, k, v, block_q=32, block_kv=32)
    want = ops.flash_attention_op(q, k, v, use_ref=True)
    assert out.dtype == dtype
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        rtol=2e-2, atol=2e-2)


def test_flash_attention_in_model_forward(rng):
    """attn_impl='pallas' produces the same logits as the 'jax' path."""
    import dataclasses
    from repro.configs.base import get_config
    from repro.models import model as M
    cfg = get_config("qwen2-1.5b").smoke()
    cfg_j = dataclasses.replace(cfg, attn_impl="jax",
                                attn_direct_max_seq=1)  # force chunked
    cfg_p = dataclasses.replace(cfg, attn_impl="pallas",
                                attn_chunk_q=16, attn_chunk_kv=16)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 32)),
                                   jnp.int32)}
    a = M.forward(params, batch, cfg_j, mode="train")["logits"]
    b = M.forward(params, batch, cfg_p, mode="train")["logits"]
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32),
                               rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("B,S,H,Hkv,dh,causal,window", [
    (2, 96, 4, 2, 32, True, None),
    (2, 64, 8, 2, 32, False, None),
    (1, 96, 4, 2, 32, True, 40),
    (1, 64, 8, 1, 16, True, None),
])
def test_flash_attention_backward(B, S, H, Hkv, dh, causal, window, rng):
    """custom_vjp (Pallas fwd + FA2 two-pass Pallas bwd) matches autodiff
    of the reference to f32 precision."""
    q = jnp.asarray(rng.normal(size=(B, S, H, dh)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, Hkv, dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, Hkv, dh)), jnp.float32)

    def loss(fn):
        return lambda *a: jnp.sum(jnp.sin(fn(*a).astype(jnp.float32)))

    gk = jax.grad(loss(lambda *a: ops.flash_attention_op(
        *a, causal=causal, window=window, block_q=32, block_kv=32)),
        argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss(lambda *a: ops.flash_attention_op(
        *a, causal=causal, window=window, use_ref=True)),
        argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gk, gr, "q k v".split()):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4,
                                   err_msg=f"d{name}")


def test_flash_attention_train_step_end_to_end(rng):
    """A full train step through attn_impl='pallas' (kernel fwd+bwd) moves
    params and matches the pure-JAX path's loss."""
    import dataclasses
    from repro.configs.base import get_config
    from repro.training.loop import init_train_state, make_train_step
    cfg = dataclasses.replace(get_config("qwen2-1.5b").smoke(),
                              attn_impl="pallas", attn_chunk_q=16,
                              attn_chunk_kv=16)
    cfg_j = dataclasses.replace(cfg, attn_impl="jax")
    state = init_train_state(cfg, jax.random.PRNGKey(0))
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 32)),
                                   jnp.int32),
             "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 32)),
                                   jnp.int32)}
    s1, m1 = jax.jit(make_train_step(cfg))(state, batch)
    s2, m2 = jax.jit(make_train_step(cfg_j))(state, batch)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=1e-4)
    np.testing.assert_allclose(float(m1["grad_norm"]),
                               float(m2["grad_norm"]), rtol=1e-3)


# ---------------------------------------------------------------------------
# ssd (Mamba2 state-space dual)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B,S,H,P,N,Q", [
    (2, 64, 3, 16, 8, 16),
    (1, 40, 2, 8, 4, 16),       # ragged chunks
    (2, 32, 4, 32, 16, 32),     # single chunk
])
def test_ssd_matches_ref(B, S, H, P, N, Q, rng):
    xs = jnp.asarray(rng.normal(size=(B, S, H, P)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.001, 0.1, (B, S, H)), jnp.float32)
    Bc = jnp.asarray(rng.normal(size=(B, S, N)), jnp.float32)
    Cc = jnp.asarray(rng.normal(size=(B, S, N)), jnp.float32)
    A = jnp.asarray(-rng.uniform(0.5, 4.0, H), jnp.float32)
    D = jnp.asarray(rng.normal(size=H), jnp.float32)
    yk, hk = ops.ssd_op(xs, dt, Bc, Cc, A, D, chunk=Q)
    yr, hr = ops.ssd_op(xs, dt, Bc, Cc, A, D, use_ref=True)
    np.testing.assert_allclose(np.asarray(yk), np.asarray(yr),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(hk), np.asarray(hr),
                               rtol=1e-4, atol=1e-5)


def test_ssd_gradients(rng):
    xs = jnp.asarray(rng.normal(size=(1, 32, 2, 8)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.001, 0.1, (1, 32, 2)), jnp.float32)
    Bc = jnp.asarray(rng.normal(size=(1, 32, 4)), jnp.float32)
    Cc = jnp.asarray(rng.normal(size=(1, 32, 4)), jnp.float32)
    A = jnp.asarray([-1.0, -2.0], jnp.float32)
    D = jnp.asarray([0.5, 0.2], jnp.float32)

    def loss(fn):
        return lambda *a: jnp.sum(jnp.sin(fn(*a)[0]))

    gk = jax.grad(loss(lambda *a: ops.ssd_op_vjp(*a, chunk=16)),
                  argnums=(0, 1, 2, 3))(xs, dt, Bc, Cc, A, D)
    gr = jax.grad(loss(lambda *a: ops.ssd_op(*a, use_ref=True)),
                  argnums=(0, 1, 2, 3))(xs, dt, Bc, Cc, A, D)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_ssd_in_mamba_block(rng):
    """ssm_impl='pallas' mamba2_block matches the chunked-jax path on a
    fresh sequence, forward and train-gradients."""
    import dataclasses
    from repro.configs.base import get_config
    from repro.models import layers as L
    cfg = get_config("zamba2-7b").smoke()
    cfg_p = dataclasses.replace(cfg, ssm_impl="pallas")
    p = L.mamba2_params(jax.random.PRNGKey(0), cfg)
    x = jnp.asarray(rng.normal(size=(2, 24, cfg.d_model)), jnp.float32)
    y0, (c0, h0) = L.mamba2_block(x, p, cfg)
    y1, (c1, h1) = L.mamba2_block(x, p, cfg_p)
    np.testing.assert_allclose(np.asarray(y0), np.asarray(y1),
                               rtol=2e-3, atol=2e-4)
    np.testing.assert_allclose(np.asarray(h0), np.asarray(h1),
                               rtol=2e-3, atol=2e-4)
    g0 = jax.grad(lambda x: jnp.sum(L.mamba2_block(x, p, cfg)[0]
                                    .astype(jnp.float32)))(x)
    g1 = jax.grad(lambda x: jnp.sum(L.mamba2_block(x, p, cfg_p)[0]
                                    .astype(jnp.float32)))(x)
    np.testing.assert_allclose(np.asarray(g0), np.asarray(g1),
                               rtol=5e-3, atol=5e-4)
