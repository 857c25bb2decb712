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
    # XLA flushes subnormal floats to zero, so a miss is decided on the
    # values the kernel compares: a 1e-45 s timer is a zero timer there.
    tiny = np.finfo(np.float32).tiny
    ftz = lambda x: np.where(np.abs(x) < tiny, np.float32(0), x)
    g, t = ftz(gaps), ftz(tpdt)[None, :]
    miss = (durs > 0) & (g >= t)
    ext = np.where(miss, np.maximum(t + t_s - g, 0.0), 0.0).sum(0)
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

