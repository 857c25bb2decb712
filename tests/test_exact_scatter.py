"""The replay's updates at repeated indices, against numpy.

A step's deliveries raise ``ready`` by a scatter-max whose destinations
repeat (``replay._deliver``: float64, or the order keys of exact clocks);
per-port times and histogram bins add at one message's rows, which repeat
only the dummy row (``simulator._shift_rows`` for clocks).  Each is held
here to numpy's ``maximum.at``/``add.at`` on float64 values 1e-6 apart
with repeated indices, on the draw of the v5e micro-check (``DRAW``; there
the chip's scatter-max agreed with numpy up to the TPU's float64
rounding of its operands, DESIGN.md §2), and the PerfBound stream on a
small fat-tree to the frozen reference with no gap at all.
"""
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ieee
from repro.core import perfbound as pb
from repro.core import replay
from repro.core import simulator as S
from repro.core.eee import Policy, PowerModel, policy_params

BENCH = Path(__file__).resolve().parents[1] / "bench"
DRAW = 12345          # the chip micro-check's seed: 64 slots, 16 nodes


def clock(x, C):
    """Host float64 values in the clock ``C``'s representation."""
    return jnp.asarray(ieee.host_bits(x) if C is ieee.ExactClock else x)


def draw(seed=DRAW):
    """64 deliveries 1e-6 apart (and a sub-ns jitter) onto 16 of 64
    nodes, as the chip micro-check drew them."""
    rng = np.random.default_rng(seed)
    for lo, hi in ((1e-7, 2e-2), (1e-7, 2e-2), (-300, 300), (0, 2e-3)):
        rng.uniform(lo, hi, 4096)        # the micro-check's earlier draws
    dst = rng.integers(0, 16, 64)
    rng.permutation(64)
    ready = rng.uniform(0.004, 0.006, 64)
    delivery = 0.005 + rng.integers(0, 1000, 64) * 1e-6 \
        + rng.uniform(0, 1e-9, 64)
    return ready, dst, delivery


@pytest.mark.parametrize("seed", [DRAW, 7, 2 ** 31 + 11])
def test_deliver_matches_numpy_maximum_at(seed):
    ready, dst, delivery = draw(seed)
    assert len(np.unique(dst)) < len(dst)
    # the tail of the step is padding: no delivery, destination node 0
    valid = np.arange(64) < 40
    dst = np.where(valid, dst, 0)
    want = ready.copy()
    np.maximum.at(want, dst, np.where(valid, delivery, -np.inf))
    for C in (ieee.FloatClock, ieee.ExactClock):
        got = jax.jit(lambda r, i, d, v: replay._deliver(r, i, d, v, C))(
            clock(ready, C), jnp.asarray(dst), clock(delivery, C),
            jnp.asarray(valid))
        np.testing.assert_array_equal(replay.host_times(got), want)


@pytest.mark.parametrize("to_node_zero", [False, True])
def test_deliver_one_message_writes_its_slot_alone(to_node_zero):
    """At most one valid message a step: slot 0 alone raises its node,
    whatever the padding slots name."""
    ready, dst, delivery = draw()
    dst = np.zeros(64, np.int64)
    dst[0] = 0 if to_node_zero else 5
    valid = np.arange(64) == 0
    want = ready.copy()
    np.maximum.at(want, dst, np.where(valid, delivery, -np.inf))
    for C in (ieee.FloatClock, ieee.ExactClock):
        got = jax.jit(lambda r, i, d, v: replay._deliver(r, i, d, v, C))(
            clock(ready, C), jnp.asarray(dst), clock(delivery, C),
            jnp.asarray(valid))
        np.testing.assert_array_equal(replay.host_times(got), want)


def test_shift_rows_matches_numpy_add_at():
    """Per-port clocks: one message's hops, unique rows, and its masked
    hops on the dummy row, which add zero; in either clock."""
    ready, dst, delivery = draw()
    n = 48                                        # links; row n is dummy
    times = np.resize(ready, n + 1)
    lp = np.array([3, 17, 40, 9, n, n])
    delta = np.where(lp < n, delivery[:6] - 0.005, 0.0)
    want = times.copy()
    np.add.at(want, lp, delta)
    for C in (ieee.FloatClock, ieee.ExactClock):
        got = jax.jit(lambda x, i, d: S._shift_rows(x, i, d, C))(
            clock(times, C), jnp.asarray(lp), clock(delta, C))
        np.testing.assert_array_equal(replay.host_times(got), want)


@pytest.mark.parametrize("kind", ["perfbound", "perfbound_correct",
                                  "perfbound_dual"])
def test_message_step_adds_match_numpy(kind):
    """One message through ``_message_step`` from a state with history:
    its per-port times and histogram bins are the state's plus the
    message's own deltas, as numpy's ``add.at`` puts them."""
    from repro.topology.fattree import FatTree
    topo = FatTree(k=4)
    pm = PowerModel()
    pol = Policy(kind=kind, bound=0.01, hist_mode="keep_all")
    p = {k: jnp.float64(v) for k, v in policy_params(pol).items()}
    net = S.init_net(topo.n_links, pol, params=p)
    rng = np.random.default_rng(DRAW)
    P = topo.n_links + 1
    for k in ("last_end", "time_wake", "time_sleep", "time_sleep2"):
        net[k] = jnp.asarray(rng.uniform(1e-4, 2e-4, P))
    net["deadline"] = net["last_end"] + 1e-6
    net["pred"] = dict(net["pred"],
                       counts=jnp.asarray(rng.integers(0, 3, (P, 200))
                                          .astype(np.float64)),
                       sums=jnp.asarray(rng.uniform(0, 1e-4, (P, 200))))
    links, dirs, nhops = (np.asarray(a) for a in topo.routes(
        np.array([0]), np.array([13])))
    H = links.shape[1]
    m = (jnp.asarray(links[0]), jnp.asarray(dirs[0]),
         jnp.asarray(nhops[0]), jnp.float64(3e-4), jnp.float64(32768.0),
         jnp.bool_(True))
    out, _ = S._message_step(net, m, pol, pm, topo.n_links, params=p)
    active, lp, dp = S._slot_rows(m[0], m[1], m[2], m[5], topo.n_links)
    g = {"free": net["dir_free"][dp], "last": net["last_end"][lp],
         "dl": net["deadline"][lp], "dl2": net["deadline2"][lp]}
    ns = S._slot_compute(g, m, active, pol, pm, p)
    lp, a = np.asarray(lp), np.asarray(ns["a"])
    assert len(set(lp[np.asarray(active)])) == int(nhops[0]) <= H
    for k, add in (("time_wake", "wake_add"), ("time_sleep", "sleep_add"),
                   ("time_sleep2", "sleep2_add")):
        want = np.asarray(net[k]).copy()
        np.add.at(want, lp, np.asarray(ns[add]) * a)
        np.testing.assert_array_equal(np.asarray(out[k])[:-1], want[:-1])
    gap = np.asarray(ns["gap"])
    do = np.asarray(active) & (gap > 0)
    b = np.asarray(pb.bin_index(ns["gap"], pol, p))
    for k, v in (("counts", do.astype(np.float64)),
                 ("sums", np.where(do, gap, 0.0))):
        want = np.asarray(net["pred"][k]).copy()
        np.add.at(want, (lp, b), v)
        np.testing.assert_array_equal(np.asarray(out["pred"][k])[:-1],
                                      want[:-1])


@pytest.fixture(scope="module")
def stream_surface():
    sys.path.insert(0, str(BENCH))
    try:
        import harness
        yield harness.load_module(BENCH / "surfaces" / "stream.py",
                                  "surface_stream_exact")
    finally:
        sys.path.remove(str(BENCH))


def test_perfbound_stream_equals_reference_on_a_small_fat_tree(
        stream_surface):
    """The benchmark's PerfBound stream, 24 windows on a k=4 fat-tree at
    16 nodes: the program's report is the frozen reference's, with no
    decision, energy or percentage apart."""
    config = json.loads((BENCH / "configs" / "fattree-k26.json")
                        .read_text())
    config["topology"]["params"], config["n_nodes"] = {"k": 4}, 16
    traffic = json.loads((BENCH / "traffic"
                          / "stream-diurnal-perfbound.json").read_text())
    s = stream_surface
    state = s.prepare(config, traffic)
    q = s.question(state, 501)
    gaps = s.compare(s.answer(q), s.reference(state, 501))
    assert gaps == {"decisions": 0, "rel_gap": 0.0, "pct_gap": 0.0,
                    "missing": 0}
    assert q["report"]["switches"] > 0
    served = {r["incumbent"] for r in q["report"]["timeline"]}
    assert served & {"pb-1pct-ds", "pbc-1pct-ds", "pbd-1pct"}
