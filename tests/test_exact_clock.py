"""Exact clocks: IEEE float64 roundings carried as bit patterns.

The TPU's float64 is a pair of float32s, so a PerfBound replay there keeps
its clocks as bit patterns with integer IEEE arithmetic
(``ieee.ExactClock``, chosen by ``simulator.clock_of``).  Here the integer
arithmetic and the exact histogram binning are held to numpy on every
kind of operand, and a PerfBound replay on exact clocks, forced on the
CPU, to the float64 replay bit for bit.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ieee, replay
from repro.core import perfbound as pb
from repro.core import simulator as S
from repro.core.eee import Policy, PowerModel, policy_params
from repro.core.sweep import sweep_cells
from repro.scenarios.spec import build_trace
from repro.scenarios.suite import resolve
from repro.topology.fattree import FatTree

OPS = {"add": (ieee.add, np.add), "sub": (ieee.sub, np.subtract),
       "mul": (ieee.mul, np.multiply)}


def operands(seed, n=50000):
    """Random float64 over 15 decades of both signs, times of a window,
    near-cancelling pairs, and the special values."""
    rng = np.random.default_rng(seed)
    x = np.concatenate([
        rng.uniform(-1, 1, n) * 10.0 ** rng.integers(-12, 3, n),
        rng.uniform(0, 0.05, n),
        [0.0, -0.0, np.inf, -np.inf, 5e-324, 2.2250738585072014e-308,
         1.7976931348623157e308, 1e-5, 2e-6, 3e-7]])
    y = np.concatenate([
        rng.uniform(-1, 1, n) * 10.0 ** rng.integers(-12, 3, n),
        x[n:2 * n] * (1 + rng.choice([-1, 1], n) * 2.0 ** -52),
        [-0.0, 0.0, -np.inf, np.inf, 5e-324, -2.2250738585072014e-308,
         1.7976931348623157e308, 1e-5, -2e-6, 3e-7]])
    return x, y


@pytest.mark.parametrize("op", sorted(OPS))
def test_integer_arithmetic_rounds_as_ieee(op):
    soft, ref = OPS[op]
    x, y = operands(7)
    with np.errstate(all="ignore"):
        want = ref(x, y)
    got = np.asarray(jax.jit(soft)(ieee.host_bits(x), ieee.host_bits(y)))
    ok = np.isfinite(x) & np.isfinite(y) if op == "mul" \
        else np.ones(len(x), bool)
    same = (got == want.view(np.uint64)) | (np.isnan(want)
                                            & np.isnan(got.view(np.float64)))
    assert (same | ~ok).all()


def test_serialization_time_as_the_cpu_divides():
    """``nbytes / bandwidth`` with a constant bandwidth is a multiply by
    the rounded reciprocal on the CPU; the integer path gives its bits."""
    rng = np.random.default_rng(3)
    nbytes = np.floor(rng.pareto(1.5, 20000) * 20000).astype(np.float64)
    cpu = np.asarray(jax.jit(lambda n: n / 50e9)(nbytes))
    soft = ieee.mul(ieee.from_int(nbytes.astype(np.int64)),
                    ieee.ExactClock.const(1.0 / 50e9))
    np.testing.assert_array_equal(np.asarray(soft).view(np.float64), cpu)


def test_keys_order_as_floats_and_convert_back():
    x, _ = operands(11, 2000)
    k = np.asarray(ieee.key(ieee.host_bits(x)))
    order = x[np.argsort(k, kind="stable")]
    assert (np.diff(order) >= 0).all()
    np.testing.assert_array_equal(np.asarray(ieee.unkey(k)),
                                  ieee.host_bits(x))
    n = np.array([0, 1, 3, 64, 32768, 2 ** 40 + 3, 2 ** 53 - 1])
    np.testing.assert_array_equal(
        np.asarray(ieee.from_int(n)).view(np.float64), n.astype(np.float64))


def test_tpu_float_conversion_within_a_unit_in_the_last_place():
    """``from_value`` reads a float64 without reinterpreting its bits, as
    the TPU must; where float64 is IEEE, as here, it is exact, also just
    below a power of two, where float32 rounds up."""
    below = np.nextafter(2.0 ** np.arange(-70, 10), 0.0)
    rng = np.random.default_rng(5)
    x = np.concatenate([
        [0.0, 1e-18, 1.39e-17, 5e-6, 0.0123, 1e-3, 3.0, np.inf, 7e-17,
         1 - 2 ** -30, -2.5e-6],
        below, below * (1 - 2 ** -40), 2.0 ** np.arange(-70, 10),
        rng.uniform(-1, 1, 5000) * 10.0 ** rng.integers(-20, 3, 5000)])
    got = np.asarray(ieee.from_value(jnp.asarray(x)))
    np.testing.assert_array_equal(got, ieee.host_bits(x))


WIDTHS = (10e-6, 1e-6, 3e-5, 2.5e-6)


@pytest.mark.parametrize("width", WIDTHS)
def test_exact_bins_are_float64_division(width):
    """A gap's bit pattern bins as ``trunc(gap / width)`` does in IEEE
    float64, clipped to the histogram: on each host-computed edge, one
    unit in the last place either side of it, on each multiple of the
    width, and on random gaps of a window."""
    pol = Policy(kind="perfbound", bound=0.01, hist_mode="keep_all",
                 hist_bin_width=width)
    B = pol.hist_bins
    edges = ieee.quotient_edges([width], B)[0].view(np.float64)
    rng = np.random.default_rng(17)
    mult = np.arange(B + 3) * width
    gap = np.concatenate([
        edges, np.nextafter(edges, 0.0), np.nextafter(edges, np.inf),
        mult, np.nextafter(mult, 0.0), np.nextafter(mult, np.inf),
        rng.uniform(0, 1.2 * B * width, 20000), rng.uniform(0, 1e-16, 100),
        [0.0, -0.0, -1e-18, -width, 1e-18, 1.39e-17, 1.0, 3e5]])
    want = np.clip((gap / width).astype(np.int64), 0, B - 1)
    p = ieee.param_bits([policy_params(pol)], B)
    got = np.asarray(jax.jit(lambda g, e: pb.bin_index(
        g, pol, {ieee.BITS + "hist_edges": e}))(
            ieee.host_bits(gap), p[ieee.BITS + "hist_edges"][0]))
    np.testing.assert_array_equal(got, want)
    # the edges are where the quotient first reaches each bin
    assert (edges / width >= np.arange(1, B)).all()
    assert (np.nextafter(edges, 0.0) / width < np.arange(1, B)).all()


def _clear():
    for f in (replay._segment_runner, replay._multi_segment_runner,
              replay._multi_init, S._compiled_chunk):
        f.cache_clear()


@pytest.fixture
def exact_clocks(monkeypatch):
    """PerfBound kinds on exact clocks, with the integer arithmetic the
    TPU runs."""
    monkeypatch.setattr(S, "EXACT_CLOCKS", True)
    _clear()
    yield
    _clear()


#: the kind whose lane reads every clock path: adaptive t_PDT and t_dst,
#: both deadlines
PBD = Policy(kind="perfbound_dual", bound=0.01, sleep_state="fast_wake",
             deep_state="deep_sleep")
MODES = ("prefix", "chain")


def _incast():
    topo = FatTree(k=4)
    spec = resolve(["dc-incast"], n_nodes=16)["dc-incast"]
    return topo, {"dc-incast": build_trace(spec, topo)}


def _grid(mode):
    topo, traces = _incast()
    with replay.wavefront_mode(mode):
        got = sweep_cells(traces, topo, {n: {"pbd": PBD} for n in traces},
                          PowerModel())
    return got["dc-incast"]["pbd"].as_dict()


@pytest.fixture(scope="module")
def float_grid():
    """The lane's statistics on float64 clocks, by executor, on demand."""
    done = {}

    def get(mode):
        if mode not in done:
            done[mode] = _grid(mode)
        return done[mode]
    return get


@pytest.mark.parametrize("mode", MODES)
def test_exact_clock_replay_is_the_float_replay(float_grid, monkeypatch,
                                                mode):
    """dc-incast on a small fat-tree, the stream's executor and the wave
    loop: the exact-clock lane's statistics equal the float64 replay's,
    every field."""
    want = float_grid(mode)
    monkeypatch.setattr(S, "EXACT_CLOCKS", True)
    _clear()
    try:
        assert S.clock_of(PBD) is ieee.ExactClock
        assert _grid(mode) == want
    finally:
        _clear()


def test_step_loop_oracle_on_exact_clocks(exact_clocks):
    """``simulate_trace_reference`` on exact clocks == on float64."""
    topo, traces = _incast()
    tr = traces["dc-incast"]
    got = S.simulate_trace_reference(tr, topo, PBD)[0].as_dict()
    S.EXACT_CLOCKS = False
    _clear()
    assert got == S.simulate_trace_reference(tr, topo, PBD)[0].as_dict()
