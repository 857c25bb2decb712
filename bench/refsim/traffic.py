"""Frozen copies of the trace format and of the trace builders the
benchmark's traffic files name.

The phase-structured trace (compute, messages, barrier per step), the
collectives' round expansions, the paper's section 4 application
generators, and the seeded datacenter arrival builders (counter-based
Philox, so a seed reproduces the same draws on any platform).  Each
builder has the signature ``fn(topo, n_nodes, seed, **params)``.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional

import numpy as np


def rng(seed: int) -> np.random.Generator:
    return np.random.Generator(np.random.Philox(seed))


@dataclass
class Step:
    compute_nodes: Optional[np.ndarray] = None   # (K,) global node ids
    compute_secs: Optional[np.ndarray] = None    # (K,) f64 seconds
    msgs: Optional[np.ndarray] = None            # (M,3) int64 [src,dst,bytes]
    barrier: bool = False


@dataclass
class Trace:
    nodes: np.ndarray                            # participating node ids
    steps: List[Step] = field(default_factory=list)
    name: str = ""
    version: int = field(default=0, repr=False, compare=False)

    # -- builder helpers -----------------------------------------------------
    def compute(self, secs):
        """Uniform (or per-node array) compute phase on all participants."""
        secs = np.broadcast_to(np.asarray(secs, np.float64),
                               self.nodes.shape).copy()
        self.steps.append(Step(compute_nodes=self.nodes.copy(),
                               compute_secs=secs))
        self.version += 1
        return self

    def messages(self, msgs, barrier=False):
        msgs = np.asarray(msgs, np.int64).reshape(-1, 3)
        self.steps.append(Step(msgs=msgs, barrier=barrier))
        self.version += 1
        return self

    def rounds(self, rounds, barrier_last=False):
        """Append a list of message rounds (each a (M,3) array)."""
        for i, r in enumerate(rounds):
            self.messages(r, barrier=barrier_last and i == len(rounds) - 1)
        return self

    def barrier(self):
        self.steps.append(Step(barrier=True))
        self.version += 1
        return self

    @property
    def n_messages(self):
        return sum(len(s.msgs) for s in self.steps if s.msgs is not None)

    @property
    def total_bytes(self):
        return sum(int(s.msgs[:, 2].sum()) for s in self.steps
                   if s.msgs is not None)


def _check_pow2(nodes):
    n = len(nodes)
    assert n >= 2 and (n & (n - 1)) == 0, \
        f"collectives require power-of-two participants, got {n}"
    return n


def _round(nodes, pairs_bytes):
    src, dst, b = zip(*pairs_bytes)
    return np.stack([nodes[np.asarray(src)], nodes[np.asarray(dst)],
                     np.asarray(b, np.int64)], axis=1)


def allreduce(nodes, nbytes):
    """Recursive halving-doubling: RS (sizes halve) then AG (sizes double)."""
    nodes = np.asarray(nodes)
    n = _check_pow2(nodes)
    logn = n.bit_length() - 1
    rounds = []
    size = nbytes
    # reduce-scatter
    for r in range(logn):
        size = max(size // 2, 1)
        peer = np.arange(n) ^ (1 << r)
        rounds.append(_round(nodes, [(i, int(peer[i]), size)
                                     for i in range(n)]))
    # all-gather
    for r in reversed(range(logn)):
        peer = np.arange(n) ^ (1 << r)
        rounds.append(_round(nodes, [(i, int(peer[i]), size)
                                     for i in range(n)]))
        size *= 2
    return rounds


def broadcast(nodes, nbytes, root=0):
    nodes = np.asarray(nodes)
    n = _check_pow2(nodes)
    logn = n.bit_length() - 1
    rounds = []
    vr = (np.arange(n) - root) % n  # virtual ranks, root -> 0
    inv = np.argsort(vr)
    # doubling: at round r only ranks vr < 2^r hold the data; each sends to
    # vr + 2^r, so the holder set doubles per round
    for r in range(logn):
        msgs = []
        for i in range(n):
            if vr[i] < (1 << r) and (vr[i] | (1 << r)) < n:
                msgs.append((i, int(inv[vr[i] | (1 << r)]), nbytes))
        if msgs:
            rounds.append(_round(nodes, msgs))
    return rounds


def reduce(nodes, nbytes, root=0):
    """Reverse binomial tree."""
    nodes = np.asarray(nodes)
    n = _check_pow2(nodes)
    logn = n.bit_length() - 1
    rounds = []
    vr = (np.arange(n) - root) % n
    inv = np.argsort(vr)
    # halving (mirror of broadcast): at round r every rank whose bit r is the
    # lowest set bit sends its accumulated partial to vr - 2^r and retires
    for r in range(logn):
        msgs = []
        for i in range(n):
            if vr[i] % (1 << (r + 1)) == (1 << r):
                msgs.append((i, int(inv[vr[i] - (1 << r)]), nbytes))
        if msgs:
            rounds.append(_round(nodes, msgs))
    return rounds


def gather(nodes, nbytes, root=0):
    """Direct gather: every rank sends its block to root (one round; the
    network serializes at the root link, as in reality)."""
    nodes = np.asarray(nodes)
    n = len(nodes)
    return [_round(nodes, [(i, root, nbytes) for i in range(n) if i != root])]


def allgather(nodes, nbytes):
    """Ring all-gather: n-1 rounds of neighbor exchanges."""
    nodes = np.asarray(nodes)
    n = len(nodes)
    return [_round(nodes, [(i, (i + 1) % n, nbytes) for i in range(n)])
            for _ in range(n - 1)]


def alltoall(nodes, nbytes_total):
    """Bruck: log2(n) rounds, each rank sends ~half its buffer 2^r away."""
    nodes = np.asarray(nodes)
    n = _check_pow2(nodes)
    logn = n.bit_length() - 1
    per_round = max(nbytes_total // 2, 1)
    rounds = []
    for r in range(logn):
        d = 1 << r
        rounds.append(_round(nodes, [(i, (i + d) % n, per_round)
                                     for i in range(n)]))
    return rounds


def p2p_halo(nodes, nbytes, dims=3):
    """Nearest-neighbor halo exchange on a pseudo-3D process grid
    (LAMMPS-style spatial decomposition): up to 2*dims neighbors each."""
    nodes = np.asarray(nodes)
    n = len(nodes)
    nx = max(int(round(n ** (1 / 3))), 1)
    ny = max(int(round((n // nx) ** 0.5)), 1) if n // nx else 1
    strides = [1, nx, nx * ny][:dims]
    msgs = []
    for s in strides:
        if s >= n:
            break
        for i in range(n):
            msgs.append((i, (i + s) % n, nbytes))
            msgs.append((i, (i - s) % n, nbytes))
    return [_round(nodes, msgs)]


def allocate(topo, n, mapping="linear", seed=0):
    assert n <= topo.n_nodes
    if mapping == "linear":
        return np.arange(n, dtype=np.int64)
    rng = np.random.default_rng(seed)
    return np.sort(rng.choice(topo.n_nodes, n, replace=False)).astype(np.int64)


def lammps(topo, n_nodes=64, iters=40, scale=1.0, mapping="linear"):
    nodes = allocate(topo, n_nodes, mapping)
    t = Trace(nodes=nodes, name="lammps")
    t.rounds(broadcast(nodes, 1 << 20))              # model distribution
    t.compute(0.8 * scale)                             # setup (Fig 6: ~1 s)
    for i in range(iters):
        t.compute(20e-3 * scale)
        t.rounds(p2p_halo(nodes, 256 << 10))         # ghost-atom exchange
        t.compute(2e-3 * scale)
        t.rounds(allreduce(nodes, 64 << 10))         # dominant collective
        if i % 10 == 9:
            t.rounds(alltoall(nodes, 512 << 10))     # FFT long-range
    t.rounds(reduce(nodes, 1 << 20), barrier_last=True)
    return t


def patmos(topo, n_nodes=64, compute_secs=1285.0, mapping="linear"):
    nodes = allocate(topo, n_nodes, mapping)
    t = Trace(nodes=nodes, name="patmos")
    t.rounds(broadcast(nodes, 8 << 20))              # input decks
    t.compute(compute_secs)                            # independent MC batches
    t.rounds(allreduce(nodes, 1 << 20))              # global mean
    t.rounds(reduce(nodes, 1 << 20), barrier_last=True)   # variance
    return t


def mlwf(topo, n_nodes=64, steps=25, layers=8, mapping="linear"):
    nodes = allocate(topo, n_nodes, mapping)
    t = Trace(nodes=nodes, name="mlwf")
    t.rounds(broadcast(nodes, 16 << 20))             # initial weights
    for s in range(steps):
        for _ in range(layers):
            t.compute(1.5e-3)
            t.rounds(gather(nodes, 128 << 10))
            t.rounds(broadcast(nodes, 128 << 10))
            t.rounds(broadcast(nodes, 64 << 10))
        t.compute(30e-3)
        t.rounds(allreduce(nodes, 8 << 20))          # gradient exchange
    t.barrier()
    return t


# AlexNet parameter counts per gradient bucket (backprop order), bytes = 4*N
_ALEXNET_LAYERS = [4_097_000, 16_781_312, 37_752_832,
                   884_736, 1_327_104, 884_736, 614_656, 34_944]


def alexnet(topo, n_nodes=64, iters=10, mapping="linear"):
    nodes = allocate(topo, n_nodes, mapping)
    t = Trace(nodes=nodes, name="alexnet")
    t.rounds(broadcast(nodes, 244 << 20))            # weights
    for _ in range(iters):
        t.compute(0.5)                                 # forward + loss
        for p in _ALEXNET_LAYERS:
            t.compute(60e-3)                           # layer backward
            t.rounds(allreduce(nodes, 4 * p))        # gradient averaging
    t.barrier()
    return t


GENERATORS = {"lammps": lammps, "patmos": patmos, "mlwf": mlwf,
              "alexnet": alexnet}


def _flow_sizes(r, n, mean_bytes):
    """Heavy-tailed flow sizes: lognormal around ``mean_bytes``, clipped to
    [64 B, 4 MiB] — mice dominate counts, elephants dominate bytes."""
    raw = r.lognormal(mean=np.log(mean_bytes), sigma=1.2, size=n)
    return np.clip(raw, 64, 4 << 20).astype(np.int64)


def _check(n_nodes, windows):
    """Degenerate-parameter guard shared by every builder: src != dst
    pairing needs two endpoints, and zero windows would synthesize an
    empty trace whose Step arrays break the dc-* plan-shape guarantee."""
    if n_nodes < 2:
        raise ValueError(f"stochastic scenarios need n_nodes >= 2 "
                         f"(got {n_nodes})")
    if windows < 1:
        raise ValueError(f"stochastic scenarios need windows >= 1 "
                         f"(got {windows})")


def _pairs(r, nodes, m, dst_weights=None):
    """m (src, dst) pairs with src != dst; optional non-uniform dst bias."""
    n = len(nodes)
    src_i = r.integers(0, n, m)
    if dst_weights is None:
        dst_i = (src_i + r.integers(1, n, m)) % n
    else:
        dst_i = r.choice(n, size=m, p=dst_weights)
        clash = dst_i == src_i
        dst_i[clash] = (dst_i[clash] + 1) % n
    return nodes[src_i], nodes[dst_i]


def _window_compute(t, r, n, window_secs, jitter):
    t.compute(r.uniform(1 - jitter, 1 + jitter, n) * window_secs)


def _emit_window(t, r, nodes, m, mean_bytes, max_flows, dst_weights=None,
                 barrier=False):
    m = int(np.clip(m, 1, max_flows))
    src, dst = _pairs(r, nodes, m, dst_weights)
    t.messages(np.stack([src, dst, _flow_sizes(r, m, mean_bytes)], axis=1),
               barrier=barrier)


def poisson(topo, n_nodes, seed, windows=24, window_secs=5e-3, rate=2000.0,
            mean_bytes=32 << 10, jitter=0.5, hot_frac=0.0, max_flows=64,
            mapping="linear"):
    """Memoryless arrivals: per window, Poisson(rate x window) flows between
    uniform (or, with ``hot_frac``, skewed) endpoint pairs."""
    _check(n_nodes, windows)
    nodes = allocate(topo, n_nodes, mapping, seed)
    t = Trace(nodes=nodes, name="poisson")
    r = rng(seed)
    w = None
    if hot_frac > 0:                  # a few hot destinations take hot_frac
        # clamp below n_nodes: every node hot would zero-divide the cold
        # weights (and make the "hot subset" meaningless)
        n_hot = max(min(n_nodes // 8, n_nodes - 1), 1)
        w = np.full(n_nodes, (1 - hot_frac) / (n_nodes - n_hot))
        w[r.choice(n_nodes, n_hot, replace=False)] = hot_frac / n_hot
    for i in range(windows):
        _window_compute(t, r, n_nodes, window_secs, jitter)
        _emit_window(t, r, nodes, r.poisson(rate * window_secs), mean_bytes,
                     max_flows, w, barrier=i == windows - 1)
    return t


def onoff(topo, n_nodes, seed, windows=24, window_secs=5e-3, rate_on=6000.0,
          rate_off=100.0, p_on=0.35, p_stay_on=0.6, mean_bytes=64 << 10,
          jitter=0.5, max_flows=64, mapping="linear"):
    """Bursty two-state (Markov-modulated) arrivals: windows flip between
    an ON state near saturation and a near-idle OFF state — the wake-storm
    regime where frame-coalescing/EEE trade-offs invert."""
    _check(n_nodes, windows)
    nodes = allocate(topo, n_nodes, mapping, seed)
    t = Trace(nodes=nodes, name="onoff")
    r = rng(seed)
    on = r.random() < p_on
    for i in range(windows):
        _window_compute(t, r, n_nodes, window_secs, jitter)
        rate = rate_on if on else rate_off
        _emit_window(t, r, nodes, r.poisson(rate * window_secs), mean_bytes,
                     max_flows, barrier=i == windows - 1)
        on = r.random() < (p_stay_on if on else p_on)
    return t


def incast(topo, n_nodes, seed, windows=24, window_secs=5e-3, fan_in=8,
           flow_bytes=256 << 10, background_rate=200.0,
           mean_bytes=16 << 10, jitter=0.5, max_flows=64, mapping="linear"):
    """Partition-aggregate incast: each window, one random aggregator pulls
    ``fan_in`` synchronized responses (serializing at its access link) over
    a trickle of background flows."""
    _check(n_nodes, windows)
    nodes = allocate(topo, n_nodes, mapping, seed)
    t = Trace(nodes=nodes, name="incast")
    r = rng(seed)
    fan_in = min(fan_in, max_flows)   # keep the one-bucket shape guarantee
    # at least one response per window: fan_in <= 0 with a quiet background
    # (m_bg == 0) would otherwise emit an EMPTY message step, changing the
    # step/shape structure the dc-* stacking guarantee depends on
    fan_in = max(min(fan_in, n_nodes - 1), 1)
    for i in range(windows):
        _window_compute(t, r, n_nodes, window_secs, jitter)
        agg = int(r.integers(0, n_nodes))
        srcs = (agg + 1 + r.choice(n_nodes - 1, fan_in,
                                   replace=False)) % n_nodes
        msgs = [[int(nodes[s]), int(nodes[agg]), int(flow_bytes)]
                for s in srcs]
        m_bg = max(0, min(int(r.poisson(background_rate * window_secs)),
                          max_flows - len(msgs)))
        if m_bg:
            src, dst = _pairs(r, nodes, m_bg)
            msgs += np.stack([src, dst, _flow_sizes(r, m_bg, mean_bytes)],
                             axis=1).tolist()
        t.messages(msgs, barrier=i == windows - 1)
    return t


def paper_app(topo, n_nodes, seed, app, **kw):
    """The paper's generators are deterministic: ``seed`` is unused."""
    return GENERATORS[app](topo, n_nodes=n_nodes, **kw)


BUILDERS = {"paper_app": paper_app, "poisson": poisson, "onoff": onoff,
            "incast": incast}


def build(builder: str, topo, n_nodes: int, seed: int, params: dict):
    return BUILDERS[builder](topo, n_nodes=n_nodes, seed=seed, **params)
