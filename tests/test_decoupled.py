"""Decoupled (per-port, kernel-backed) policy evaluation vs the coupled
simulator — the TPU-native fast path's approximation contract."""
import numpy as np
import pytest

from repro.core import decoupled as D
from repro.core import simulator as S
from repro.core.eee import Policy, PowerModel
from repro.traffic.generators import small_apps
from repro.traffic.trace import Trace


def _events(topo, pm, trace):
    base = Policy(kind="none")
    res, events = S.simulate_trace(trace, topo, base, pm,
                                   collect_events=True)
    return res, events


def test_events_to_streams_basic(topo, pm):
    nodes = np.arange(2, dtype=np.int64)
    tr = Trace(nodes=nodes, name="t")
    tr.messages([[0, 1, 50_000_000]])           # 1 ms serialization
    tr.compute(0.01)
    tr.messages([[0, 1, 50_000_000]], barrier=True)
    res, events = _events(topo, pm, tr)
    gaps, durs, tail = D.events_to_streams(events, topo.n_links,
                                           res.makespan)
    g, d = np.asarray(gaps), np.asarray(durs)
    used = np.nonzero(d.sum(0))[0]
    assert len(used) == 2                        # the two node links
    for l in used:
        busy = d[:, l].sum()
        np.testing.assert_allclose(busy, 2e-3, rtol=1e-6)
    # gap before second transmission ~ 10 ms compute
    second_gaps = np.sort(g[:, used[0]])[::-1]
    assert second_gaps[0] >= 0.9e-2


def test_overlapping_intervals_merged(topo, pm):
    """Both directions of a duplex link merge into one busy window."""
    nodes = np.arange(2, dtype=np.int64)
    tr = Trace(nodes=nodes, name="t")
    tr.messages([[0, 1, 50_000_000], [1, 0, 50_000_000]], barrier=True)
    res, events = _events(topo, pm, tr)
    gaps, durs, tail = D.events_to_streams(events, topo.n_links,
                                           res.makespan)
    d = np.asarray(durs)
    used = np.nonzero(d.sum(0))[0]
    for l in used:
        n_intervals = (d[:, l] > 0).sum()
        assert n_intervals == 1                  # merged duplex overlap


def test_decoupled_matches_coupled_hit_miss_counts(topo, pm):
    """For a fixed-PDT policy on a sparse trace (no queueing feedback) the
    decoupled replay reproduces the coupled simulator's transition counts
    and energy to first order."""
    tr = small_apps(topo, n_nodes=8)["alexnet"]
    res0, events = _events(topo, pm, tr)

    for t_pdt in (10e-6, 1e-3, 0.1):
        pol = Policy(kind="fixed", t_pdt=t_pdt, sleep_state="deep_sleep")
        coupled, _ = S.simulate_trace(tr, topo, pol, pm)
        gaps, durs, tail = D.events_to_streams(events, topo.n_links,
                                               res0.makespan)
        dec = D.evaluate_fixed(gaps, durs, tail, t_pdt, pol, pm,
                               interpret=True)
        n_wake_dec = float(np.asarray(dec["n_wake"]).sum())
        # counts agree within 15 % (feedback shifts borderline gaps)
        if coupled.n_wake_transitions:
            assert abs(n_wake_dec - coupled.n_wake_transitions) \
                <= 0.15 * coupled.n_wake_transitions + 2
        # link energy within 10 %
        assert abs(dec["link_energy"] - coupled.link_energy) \
            <= 0.10 * coupled.link_energy


def test_sweep_policies_monotone_energy(topo, pm):
    """Across t_PDT values, wake time is monotone non-decreasing in t_PDT
    (more conservative -> more awake) on a fixed event stream."""
    tr = small_apps(topo, n_nodes=8)["lammps"]
    res0, events = _events(topo, pm, tr)
    pol = Policy(kind="fixed", sleep_state="deep_sleep")
    sweep = D.sweep_policies(events, topo.n_links, res0.makespan,
                             [0.0, 1e-5, 1e-4, 1e-3, 1e-2, 1.0], pol, pm,
                             interpret=True)
    keys = sorted(sweep)
    wake = [sweep[t]["wake_time"] for t in keys]
    # monotone up to the transition-overhead slack: raising t_PDT past a gap
    # g trades (t_PDT_old + t_s + t_w) for g — each such crossing may REDUCE
    # wake time by at most t_w + t_s, so allow that much per lost transition
    st = pol.state
    for (ta, a), (tb, b) in zip(zip(keys, wake), zip(keys[1:], wake[1:])):
        lost = float(np.asarray(sweep[ta]["n_wake"]).sum()
                     - np.asarray(sweep[tb]["n_wake"]).sum())
        assert b >= a - max(lost, 0) * (st.t_w + st.t_s) - 1e-6
    # t_PDT = 1 s on a ~2 s trace: essentially always-on
    full = sweep[1.0]["wake_time"] + sweep[1.0]["sleep_time"]
    assert sweep[1.0]["wake_time"] > 0.5 * full


def test_perfbound_snapshot_prediction(topo, pm):
    """Kernel-backed one-shot PerfBound: bimodal gaps (many short, few very
    long) must select a t_PDT between the modes."""
    rng = np.random.default_rng(0)
    P = 8
    short = rng.uniform(1e-5, 5e-5, (400, P))
    lng = rng.uniform(0.5, 1.0, (20, P))
    gaps = np.concatenate([short, lng]).astype(np.float32)
    pol = Policy(kind="perfbound", bound=0.01, hist_bin_width=10e-6,
                 max_tpdt=10e-3, sleep_state="deep_sleep")
    t = D.perfbound_snapshot_tpdt(gaps, t_elapsed=20.0, hop_mean=3.0,
                                  policy=pol, interpret=True)
    t = np.asarray(t)
    # budget N = 0.01/3 * 20 / 4.48e-6 ~ 1.5e4 >> 420 samples: everything is
    # affordable -> t_PDT lands at/below the short mode (aggressive)
    assert (t <= 1e-4).all()
    # a tight window (X small) forces conservative prediction
    t2 = np.asarray(D.perfbound_snapshot_tpdt(
        gaps, t_elapsed=1e-3, hop_mean=3.0, policy=pol, interpret=True))
    assert (t2 >= t).all()


def test_ref_and_kernel_paths_agree_end_to_end(topo, pm):
    tr = small_apps(topo, n_nodes=8)["mlwf"]
    res0, events = _events(topo, pm, tr)
    gaps, durs, tail = D.events_to_streams(events, topo.n_links,
                                           res0.makespan)
    pol = Policy(kind="fixed", sleep_state="fast_wake")
    a = D.evaluate_fixed(gaps, durs, tail, 1e-4, pol, pm, interpret=True)
    b = D.evaluate_fixed(gaps, durs, tail, 1e-4, pol, pm, use_ref=True)
    np.testing.assert_allclose(a["link_energy"], b["link_energy"],
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(a["n_wake"]),
                               np.asarray(b["n_wake"]))


def test_decoupled_dual_mode_ladder(topo, pm):
    """The dual-mode per-port evaluation: kernel == ref, the ladder's
    energy sits between fast-wake-only and deep-sleep-only on the same
    streams, and long gaps land in the deep account."""
    tr = small_apps(topo, n_nodes=8)["lammps"]
    res0, events = _events(topo, pm, tr)
    gaps, durs, tail = D.events_to_streams(events, topo.n_links,
                                           res0.makespan)
    t_pdt = 1e-5
    fw = Policy(kind="fixed", t_pdt=t_pdt, sleep_state="fast_wake")
    ds = Policy(kind="fixed", t_pdt=t_pdt, sleep_state="deep_sleep")
    dual = Policy(kind="dual", t_pdt=t_pdt, t_dst=1e-4,
                  sleep_state="fast_wake", deep_state="deep_sleep")
    out = {}
    for name, pol in (("fw", fw), ("ds", ds), ("dual", dual)):
        a = D.evaluate_fixed(gaps, durs, tail, t_pdt, pol, pm,
                             interpret=True)
        b = D.evaluate_fixed(gaps, durs, tail, t_pdt, pol, pm, use_ref=True)
        for k in ("link_energy", "wake_time", "sleep_time", "sleep2_time"):
            np.testing.assert_allclose(a[k], b[k], rtol=1e-5, atol=1e-8,
                                       err_msg=f"{name}.{k}")
        out[name] = a
    assert float(np.asarray(out["dual"]["n_deep"]).sum()) > 0
    assert out["dual"]["sleep2_time"] > 0
    assert out["fw"]["sleep2_time"] == out["ds"]["sleep2_time"] == 0.0
    assert out["ds"]["link_energy"] <= out["dual"]["link_energy"] \
        <= out["fw"]["link_energy"]
