"""The benchmark's yardstick on the CPU: hop counts, rates, tails, and
the reduction of a profiler trace."""
import json
from pathlib import Path

import numpy as np
import pytest

import metric_math as mm
import xplane_reduce as xr
from refsim import topology as ref_topology
from refsim import traffic as ref_traffic

HERE = Path(__file__).resolve().parent
CATALOG_SEEDS = {"dc-poisson": 31, "dc-hotspot": 32, "dc-onoff": 33,
                 "dc-incast": 34}


def config(name):
    return json.loads((HERE / "configs" / f"{name}.json").read_text())


def traffic(name):
    return json.loads((HERE / "traffic" / f"{name}.json").read_text())


def build(cfg, scenario, seed):
    topo = ref_topology.build(cfg["topology"])
    return topo, ref_traffic.build(scenario["builder"], topo, cfg["n_nodes"],
                                   seed, scenario["params"])


def test_lammps_hops_on_megafly4160():
    cfg = config("megafly4160")
    (sc,) = traffic("perfbound-lammps")["scenarios"]
    topo, tr = build(cfg, sc, 41)
    assert (topo.n_nodes, topo.n_links, topo.n_switches) == (4160, 10400,
                                                              1040)
    assert tr.n_messages == 12030 and len(tr.steps) == 169
    assert mm.trace_hops(tr, topo) == 36522


def test_dc_hops_on_fattree_k26():
    cfg = config("fattree-k26")
    hops = {}
    for sc in traffic("grid-dc")["scenarios"]:
        topo, tr = build(cfg, sc, CATALOG_SEEDS[sc["name"]])
        hops[sc["name"]] = mm.trace_hops(tr, topo)
    assert (topo.n_nodes, topo.n_links) == (4394, 13182)
    assert hops == {"dc-poisson": 838, "dc-hotspot": 1010, "dc-onoff": 822,
                    "dc-incast": 758}
    assert sum(hops.values()) == 3428


@pytest.mark.parametrize("name,cfg", [("app-lammps", "megafly4160"),
                                      ("dc-onoff", "fattree-k26"),
                                      ("dc-hotspot", "megafly4160")])
def test_frozen_traces_match_the_program(name, cfg):
    """At this commit the frozen builders and routes give the program's
    traces and routes message for message."""
    from repro.scenarios import build_trace, get_scenario
    from repro.topology.fattree import FatTree
    from repro.topology.megafly import Megafly
    c = config(cfg)
    prog_topo = {"megafly": Megafly, "fattree": FatTree}[
        c["topology"]["kind"]](**c["topology"]["params"])
    spec = get_scenario(name).scaled(c["n_nodes"], 977)
    got = build_trace(spec, prog_topo)
    scs = {s["name"]: s for t in ("perfbound-lammps", "grid-dc")
           for s in traffic(t)["scenarios"]}
    topo, want = build(c, scs[name], 977)
    assert len(got.steps) == len(want.steps)
    for g, w in zip(got.steps, want.steps):
        for k in ("compute_nodes", "compute_secs", "msgs"):
            a, b = getattr(g, k), getattr(w, k)
            assert (a is None) == (b is None)
            if a is not None:
                np.testing.assert_array_equal(a, b)
                if k == "msgs" and len(a):
                    for x, y in zip(prog_topo.routes(a[:, 0], a[:, 1]),
                                    topo.routes(b[:, 0], b[:, 1])):
                        np.testing.assert_array_equal(x, y)
        assert g.barrier == w.barrier


def test_derive_seed_is_fixed_and_distinct():
    big = 2 ** 31 + 12345
    assert mm.derive_seed(big, 0) == mm.derive_seed(big, 0)
    seeds = {mm.derive_seed(big, i) for i in range(-1, 200)}
    assert len(seeds) == 201
    assert all(0 <= s < 2 ** 63 for s in seeds)


def test_window_rate_counts_all_work_over_all_time():
    assert mm.window_rate([100, 300], [1.0, 3.0]) == 100.0
    assert mm.window_rate([], []) is None


@pytest.mark.parametrize("n,q,want,beyond", [
    (200, 95, 190.0, 10), (20, 95, 19.0, 1), (1, 95, 1.0, 0),
    (100, 50, 50.0, 50)])
def test_percentile_nearest_rank_with_count(n, q, want, beyond):
    vals = np.random.default_rng(0).permutation(np.arange(1, n + 1))
    assert mm.percentile(vals, q) == (want, n, beyond)


def test_percentile_of_nothing():
    assert mm.percentile([], 95) == (None, 0, 0)


def test_union_merges_overlaps():
    assert xr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]


def test_reduce_recorded_cpu_trace():
    """A trace recorded on the CPU: three questions, each one jitted call,
    a 5 ms host sleep inside ``bench.host_prep``, and another jitted call,
    all inside ``bench.window``."""
    out = xr.reduce_file(HERE / "testdata" / "cpu_trace.xplane.pb", "cpu")
    assert out["devices"] == 1
    assert 0 < out["busy_s"] < out["window_s"]
    ops = [s for _, s in out["device_ops"]]
    assert ops == sorted(ops, reverse=True) and ops[0] > 0
    assert "dot_general.1" in dict(out["device_ops"])
    gaps = out["idle_gaps"]
    assert [g for g, _ in gaps[:3]] == ["bench.host_prep"] * 3
    assert all(0.004 < s < 0.02 for _, s in gaps[:3])
    # busy and the gaps tile the window
    assert len(gaps) <= 10
    assert out["window_s"] >= out["busy_s"] + sum(s for _, s in gaps[:3])


def test_reduce_finds_nothing_without_a_device_line():
    out = xr.reduce_file(HERE / "testdata" / "cpu_trace.xplane.pb", "tpu")
    assert out is None
