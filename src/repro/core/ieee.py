"""IEEE 754 binary64 arithmetic on bit patterns, exact on every backend.

The TPU has no float64 unit: XLA carries a float64 as a pair of float32s,
about 48 significant bits where IEEE has 53, so every add, subtract and
multiply rounds differently from the host's.  PerfBound records a port's
inactivity gap as ``t_avail - last``; a message queued behind another sees
a gap that is zero or a rounding residue of the two ports' clocks, and a
positive residue lands in bin 0 and becomes the port's t_PDT.  Matching
the float64 reference therefore needs the reference's roundings, bit for
bit, in the replay's clock arithmetic.

Here a float64 travels as its ``uint64`` bit pattern.  ``add``/``sub``
round to nearest, ties to even, as IEEE does, by integer arithmetic,
which the TPU performs exactly; ``mul`` likewise for finite operands.
``key`` maps a pattern to an integer that orders as the float does, so
comparisons and maxima never leave the integers.  The operations are
jitted functions: a replay calls them hundreds of times, and a jitted
call traces and lowers once per shape, where the sixty-odd integer
operations inlined at every call took minutes to set up.
``simulator.clock_of`` chooses these clocks on the TPU; elsewhere the
float64 unit is IEEE already and the replay keeps plain float64
(``FloatClock``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

U64 = jnp.uint64
_SIGN = 1 << 63
_MAG = _SIGN - 1
_FRAC = (1 << 52) - 1
_HIDDEN = 1 << 52
_INF = 0x7FF << 52
_NAN = 0x7FF8 << 48


def _u(v) -> jnp.ndarray:
    return jnp.asarray(v, U64)


def host_bits(x) -> np.ndarray:
    """The bit pattern of host float64 values."""
    return np.asarray(x, np.float64).view(np.uint64)


def value(b) -> jnp.ndarray:
    """The float64 of a bit pattern (on the TPU rounded to its float64)."""
    return lax.bitcast_convert_type(_u(b), jnp.float64)


def key(b) -> jnp.ndarray:
    """An unsigned integer that orders as the float64 of ``b`` does
    (-0.0 and +0.0 apart, NaN excluded)."""
    b = _u(b)
    return jnp.where(b >> 63 == 1, ~b, b | _u(_SIGN))


def _unpack(b):
    e = ((b >> 52) & 0x7FF).astype(jnp.int64)
    m = b & _u(_FRAC)
    m = jnp.where(e > 0, m | _u(_HIDDEN), m)
    return b >> 63, e, m


def _round_pack(s, e, m):
    """Pack sign ``s``, exponent ``e`` (>= 1) and a significand ``m`` that
    carries 3 guard bits below its 53, rounding to nearest, ties to even;
    a significand without its hidden bit at ``e`` = 1 is subnormal."""
    g = m & 7
    m = m >> 3
    up = (g > 4) | ((g == 4) & ((m & 1) == 1))
    m = m + up.astype(U64)
    carry = (m >> 53) != 0
    m = jnp.where(carry, m >> 1, m)
    e = jnp.where(carry, e + 1, e)
    efield = jnp.where((m & _u(_HIDDEN)) != 0, e, 0)
    out = (s << 63) | (efield.astype(U64) << 52) | (m & _u(_FRAC))
    return jnp.where(efield >= 0x7FF, (s << 63) | _u(_INF), out)


@jax.jit
def add(a, b):
    """``a + b`` on bit patterns, as IEEE binary64 rounds it."""
    a, b = jnp.broadcast_arrays(_u(a), _u(b))
    swap = (b & _u(_MAG)) > (a & _u(_MAG))
    x, y = jnp.where(swap, b, a), jnp.where(swap, a, b)
    sx, ex_raw, mx = _unpack(x)
    sy, ey_raw, my = _unpack(y)
    ex, ey = jnp.maximum(ex_raw, 1), jnp.maximum(ey_raw, 1)
    mx, my = mx << 3, my << 3
    d = jnp.minimum(ex - ey, 63).astype(U64)
    lost = my & ((_u(1) << d) - 1)
    my = (my >> d) | (lost != 0).astype(U64)
    same = sx == sy
    m = jnp.where(same, mx + my, mx - my)
    e = ex
    over = (m >> 56) != 0
    m = jnp.where(over, (m >> 1) | (m & 1), m)
    e = jnp.where(over, e + 1, e)
    lz = lax.clz(m).astype(jnp.int64) - 8
    sh = jnp.clip(jnp.minimum(lz, e - 1), 0, 63)
    m = m << sh.astype(U64)
    e = e - sh
    out = _round_pack(sx, e, m)
    out = jnp.where(m == 0, jnp.where(same, sx << 63, _u(0)), out)
    nan = (ex_raw == 0x7FF) & ((x & _u(_FRAC)) != 0)
    inf_x = (ex_raw == 0x7FF) & ~nan
    clash = inf_x & (ey_raw == 0x7FF) & ~same
    out = jnp.where(inf_x, x, out)
    return jnp.where(nan | clash, _u(_NAN), out)


@jax.jit
def sub(a, b):
    """``a - b`` on bit patterns, as IEEE binary64 rounds it."""
    return add(a, _u(b) ^ _u(_SIGN))


@jax.jit
def mul(a, b):
    """``a * b`` on bit patterns of finite operands, as IEEE binary64
    rounds it (the product of the significands in 32-bit limbs)."""
    a, b = jnp.broadcast_arrays(_u(a), _u(b))
    sa, ea, ma = _unpack(a)
    sb, eb, mb = _unpack(b)
    ea, eb = jnp.maximum(ea, 1), jnp.maximum(eb, 1)
    # subnormal significands normalized, their exponents lowered
    la = lax.clz(ma).astype(jnp.int64) - 11
    lb = lax.clz(mb).astype(jnp.int64) - 11
    la, lb = jnp.clip(la, 0, 63), jnp.clip(lb, 0, 63)
    ma, mb = ma << la.astype(U64), mb << lb.astype(U64)
    ea, eb = ea - la, eb - lb
    lo32 = _u(0xFFFFFFFF)
    a0, a1, b0, b1 = ma & lo32, ma >> 32, mb & lo32, mb >> 32
    p00, p11 = a0 * b0, a1 * b1
    mid = a0 * b1 + a1 * b0
    lo = p00 + ((mid & lo32) << 32)
    hi = p11 + (mid >> 32) + (lo < p00).astype(U64)
    top = (hi >> 41) & 1                      # product bit 105 set?
    s = (49 + top).astype(U64)
    m = (hi << (_u(64) - s)) | (lo >> s)
    m = m | ((lo & ((_u(1) << s) - 1)) != 0).astype(U64)
    e = ea + eb - 1023 + top.astype(jnp.int64)
    # results below the normal range: shift into the subnormal significand
    under = jnp.clip(1 - e, 0, 63)
    lost = m & ((_u(1) << under.astype(U64)) - 1)
    m = jnp.where(under > 0, (m >> under.astype(U64)) | (lost != 0)
                  .astype(U64), m)
    e = jnp.maximum(e, 1)
    out = _round_pack(sa ^ sb, e, m)
    zero = (ma == 0) | (mb == 0)
    return jnp.where(zero, (sa ^ sb) << 63, out)


def maximum(a, b):
    """The larger of two bit patterns, as floats order them."""
    return jnp.where(key(a) >= key(b), _u(a), _u(b))


def unkey(k) -> jnp.ndarray:
    """The bit pattern whose ``key`` is ``k``."""
    k = _u(k)
    return jnp.where(k >> 63 == 1, k ^ _u(_SIGN), ~k)


@jax.jit
def from_int(n) -> jnp.ndarray:
    """The bit pattern of a non-negative integer below 2**53, exactly."""
    n = jnp.asarray(n, jnp.int64).astype(U64)
    sh = jnp.clip(lax.clz(n).astype(jnp.int64) - 11, 0, 63)
    m = n << sh.astype(U64)
    out = ((1075 - sh).astype(U64) << 52) | (m & _u(_FRAC))
    return jnp.where(n == 0, _u(0), out)


@jax.jit
def from_value(x):
    """The bit pattern of a float64 value without reinterpreting its bits,
    which the TPU cannot do: scaled by powers of two into [2**52, 2**54)
    and truncated to an integer.  Exact where float64 is IEEE; on the TPU
    within a unit in the last place of the value it holds."""
    ax = jnp.abs(x)
    hi = lax.bitcast_convert_type(ax.astype(jnp.float32), jnp.uint32)
    e = ((hi >> 23) & 0xFF).astype(jnp.int64) - 127
    k = jnp.clip(52 - e, -252, 254)

    def pow2(n):                          # 2**n, n in [-126, 127]
        return lax.bitcast_convert_type(
            ((n + 127) << 23).astype(jnp.uint32),
            jnp.float32).astype(jnp.float64)
    half = k // 2
    y = ax * pow2(half) * pow2(k - half)
    # float32 rounded a value just below a power of two up to it
    low = y < 2.0 ** 52
    y = jnp.where(low, y * 2.0, y)
    k = k + low.astype(jnp.int64)
    m = y.astype(jnp.int64).astype(U64)
    top = (m >> 53) != 0
    m = jnp.where(top, m >> 1, m)
    ef = 1075 - k + top.astype(jnp.int64)
    out = (ef.astype(U64) << 52) | (m & _u(_FRAC))
    out = jnp.where(ax == 0, _u(0), out)
    out = jnp.where(jnp.isinf(ax), _u(_INF), out)
    return jnp.where(x < 0, out | _u(_SIGN), out)


class FloatClock:
    """Times as float64: the kinds whose decisions never rest on a
    rounding residue (they record no inactivity gaps)."""
    dtype = jnp.float64
    lowest = -jnp.inf       # the key of "no time", below every time
    top = jnp.inf
    add = staticmethod(jnp.add)
    sub = staticmethod(jnp.subtract)
    maximum = staticmethod(jnp.maximum)

    @staticmethod
    def ge(a, b):
        return a >= b

    @staticmethod
    def lt(a, b):
        return a < b

    @staticmethod
    def key(x):
        return x

    @staticmethod
    def unkey(k):
        return k

    @staticmethod
    def val(x):
        return x

    @staticmethod
    def of(x):
        return x

    @staticmethod
    def const(x: float):
        return x

    @staticmethod
    def param(p: dict, name: str):
        return p[name]

    @staticmethod
    def tser(nbytes, bandwidth: float):
        return nbytes / bandwidth


class ExactClock:
    """Times as float64 bit patterns with IEEE roundings, in the integer
    arithmetic above on every backend.  ``param`` reads a policy number's
    host bits (``param_bits``); ``tser`` is a frame's serialization time
    as the CPU computes ``nbytes / bandwidth``, a multiply by the rounded
    reciprocal."""
    dtype = U64
    lowest = np.uint64(0)
    top = np.uint64(2 ** 64 - 1)
    add = staticmethod(add)
    sub = staticmethod(sub)
    maximum = staticmethod(maximum)
    key = staticmethod(key)
    unkey = staticmethod(unkey)
    val = staticmethod(value)
    of = staticmethod(from_value)

    @staticmethod
    def ge(a, b):
        return key(a) >= key(b)

    @staticmethod
    def lt(a, b):
        return key(a) < key(b)

    @staticmethod
    def const(x: float):
        return np.uint64(host_bits(x))

    @staticmethod
    def param(p: dict, name: str):
        b = p.get(BITS + name)
        return ExactClock.const(p[name]) if b is None else b

    @staticmethod
    def tser(nbytes, bandwidth: float):
        return mul(from_int(nbytes.astype(jnp.int64)),
                   ExactClock.const(1.0 / bandwidth))


#: the prefix of a policy number's bit pattern in a parameter dict
BITS = "bits_"
#: the policy numbers that enter the replay's clock arithmetic
CLOCK_PARAMS = ("t_w", "t_s", "t_w2", "t_s2", "sync_overhead", "t_dst",
                "t_pdt", "tpdt_init")


def quotient_edges(divisor, n: int) -> np.ndarray:
    """For each ``divisor`` (B,), the bit patterns (B, n - 1) of the
    smallest float64 ``g`` whose IEEE quotient ``g / divisor`` is at least
    j, for j = 1 .. n - 1: the integer part of a non-negative quotient,
    clipped to n - 1, is then the count of edges at or below ``g``."""
    w = np.asarray(divisor, np.float64)[..., None]
    j = np.arange(1, n, dtype=np.float64)
    g = j * w
    while True:                          # (j * w) / w is within an ulp of j
        down = np.nextafter(g, 0.0)
        lower = down / w >= j
        if not lower.any():
            break
        g = np.where(lower, down, g)
    while True:
        higher = g / w < j
        if not higher.any():
            break
        g = np.where(higher, np.nextafter(g, np.inf), g)
    return host_bits(g)


def param_bits(cols: list, hist_bins: int = 0) -> dict:
    """``{BITS + name: (B,) uint64}`` of the ``CLOCK_PARAMS`` of B
    parameter dicts (``eee.policy_params``), from the host's float64;
    with ``hist_bins``, also ``BITS + "hist_edges"`` (B, hist_bins - 1),
    the linear histogram's bin edges (``quotient_edges``)."""
    out = {BITS + f: jnp.asarray(host_bits([c[f] for c in cols]))
           for f in CLOCK_PARAMS}
    if hist_bins:
        out[BITS + "hist_edges"] = jnp.asarray(quotient_edges(
            [c["hist_bin_width"] for c in cols], hist_bins))
    return out
