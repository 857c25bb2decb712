"""Batched policy-sweep engine: equivalence with the serial simulator,
static-structure grouping, and energy-accounting invariants."""
import numpy as np
import pytest

from repro.core import simulator as S
from repro.core import sweep as W
from repro.core.eee import (PARAM_FIELDS, Policy, policy_params, static_key)
from repro.traffic.generators import small_apps
from repro.traffic.trace import Trace

CHECK_FIELDS = ("makespan", "mean_latency", "max_latency", "n_messages",
                "link_energy", "switch_energy", "node_energy", "total_energy",
                "asleep_frac", "deep_frac", "n_wake_transitions", "hits", "misses",
                "deep_misses")


def _mini_trace(topo, n=12, seed=3):
    """A small Megafly trace with compute phases, cross-group traffic and
    barriers — enough structure to exercise latency feedback."""
    rng = np.random.default_rng(seed)
    nodes = np.arange(n, dtype=np.int64) * (topo.n_nodes // n)
    tr = Trace(nodes=nodes, name="mini")
    for r in range(4):
        tr.compute(rng.uniform(1e-5, 2e-3, n))
        msgs = [[int(nodes[i]), int(nodes[(i + 1 + r) % n]),
                 int(rng.integers(256, 1 << 16))] for i in range(n)]
        tr.messages(msgs, barrier=(r % 2 == 1))
    tr.compute(5e-3)
    tr.messages([[int(nodes[0]), int(nodes[-1]), 4096]], barrier=True)
    return tr


GRID = {
    "none": Policy(kind="none"),
    "fixed/fw/10us": Policy(kind="fixed", t_pdt=1e-5, sleep_state="fast_wake"),
    "fixed/ds/100us": Policy(kind="fixed", t_pdt=1e-4,
                             sleep_state="deep_sleep"),
    "fixed/ds/0": Policy(kind="fixed", t_pdt=0.0, sleep_state="deep_sleep"),
    "pb/ds/1pct": Policy(kind="perfbound", bound=0.01,
                         sleep_state="deep_sleep"),
    "pb/fw/5pct": Policy(kind="perfbound", bound=0.05,
                         sleep_state="fast_wake"),
    "pb/ds/ring": Policy(kind="perfbound", bound=0.01, hist_mode="circular",
                         ring_n=64, sleep_state="deep_sleep"),
    "pb/ds/clear": Policy(kind="perfbound", bound=0.02,
                          hist_mode="self_clear", hist_clear_n=50,
                          sleep_state="deep_sleep"),
    "pbc/ds/1pct": Policy(kind="perfbound_correct", bound=0.01,
                          sleep_state="deep_sleep"),
    "pbc/fw/2pct": Policy(kind="perfbound_correct", bound=0.02,
                          sleep_state="fast_wake"),
    # log-spaced bins and recency decay: the two configurations whose
    # batched program takes traced-param branches the serial path doesn't
    # (jnp bin_centers / per-lane hist_decay) — two lanes each so the
    # batch axis is genuinely exercised
    "pb/ds/log": Policy(kind="perfbound", bound=0.01, hist_log_bins=True,
                        sleep_state="deep_sleep"),
    "pb/fw/log8": Policy(kind="perfbound", bound=0.02, hist_log_bins=True,
                         hist_log_min=1e-8, sleep_state="fast_wake"),
    "pbc/ds/decay98": Policy(kind="perfbound_correct", bound=0.01,
                             hist_decay=0.98, sleep_state="deep_sleep"),
    "pbc/fw/decay9": Policy(kind="perfbound_correct", bound=0.02,
                            hist_decay=0.9, sleep_state="fast_wake"),
    # dual-mode FSM kinds (DESIGN.md §6): two lanes per kind so the batch
    # axis carries genuinely different ladder/coalescing numerics
    "dual/fast": Policy(kind="dual", t_pdt=1e-5, t_dst=5e-5,
                        sleep_state="fast_wake", deep_state="deep_sleep"),
    "dual/slow": Policy(kind="dual", t_pdt=1e-4, t_dst=2e-3,
                        sleep_state="fast_wake", deep_state="deep_sleep"),
    "coal/on": Policy(kind="coalesce", t_pdt=1e-5, t_dst=2e-4,
                      max_delay=5e-5, max_frames=8,
                      sleep_state="fast_wake", deep_state="deep_sleep"),
    "coal/off": Policy(kind="coalesce", t_pdt=1e-5, t_dst=2e-4,
                       max_delay=5e-5, max_frames=1,
                       sleep_state="fast_wake", deep_state="deep_sleep"),
    "pbd/1pct": Policy(kind="perfbound_dual", bound=0.01,
                       sleep_state="fast_wake", deep_state="deep_sleep"),
    "pbd/5pct": Policy(kind="perfbound_dual", bound=0.05, t_dst=1e-4,
                       sleep_state="fast_wake", deep_state="deep_sleep"),
    # predictive FSM kinds (DESIGN.md §8): hold-at-source coalescing and
    # the forecast-driven timer ladder, two lanes each
    "pre/fast": Policy(kind="precoalesce", t_pdt=1e-5, t_dst=2e-4,
                       hold_delay=2e-5, hold_frames=4,
                       sleep_state="fast_wake", deep_state="deep_sleep"),
    "pre/slow": Policy(kind="precoalesce", t_pdt=1e-5, t_dst=2e-4,
                       hold_delay=2e-4, hold_frames=16,
                       sleep_state="fast_wake", deep_state="deep_sleep"),
    "pred/soft": Policy(kind="predict", t_pdt=1e-5, t_dst=2e-4,
                        forecast_weight=0.5, forecast_margin=2.0,
                        sleep_state="fast_wake", deep_state="deep_sleep"),
    "pred/hard": Policy(kind="predict", t_pdt=1e-5, t_dst=2e-4,
                        forecast_weight=1.0, forecast_margin=8.0,
                        sleep_state="fast_wake", deep_state="deep_sleep"),
}


# ---------------------------------------------------------------------------
# Policy factoring: static structure vs numeric parameter vector
# ---------------------------------------------------------------------------


def test_policy_params_covers_param_fields():
    p = policy_params(Policy(kind="fixed", t_pdt=3e-5,
                             sleep_state="fast_wake"))
    assert set(p) == set(PARAM_FIELDS)
    assert p["t_pdt"] == 3e-5
    assert p["t_w"] == Policy(sleep_state="fast_wake").state.t_w
    assert all(isinstance(v, float) for v in p.values())


def test_static_key_ignores_numeric_fields():
    a = Policy(kind="perfbound", bound=0.01, sleep_state="deep_sleep")
    b = Policy(kind="perfbound", bound=0.05, sleep_state="fast_wake",
               t_pdt=1.0, max_tpdt=1e-2, hist_bin_width=1e-5)
    assert static_key(a) == static_key(b)
    assert static_key(a) != static_key(Policy(kind="perfbound_correct"))
    assert static_key(a) != static_key(
        Policy(kind="perfbound", hist_mode="circular"))
    # decay participates only as a flag
    assert static_key(Policy(hist_decay=0.9)) == static_key(
        Policy(hist_decay=0.5))
    assert static_key(Policy(hist_decay=0.9)) != static_key(Policy())


def test_grouping_batches_paper_grid():
    """A paper-style 2x2x2 perfbound grid shares ONE static structure, so
    the ≥8-policy sweep runs as a single batched scan per chunk."""
    pols = {f"pb/{st}/{b}/{w:g}":
            Policy(kind="perfbound", bound=b, sleep_state=st,
                   hist_bin_width=w)
            for st in ("fast_wake", "deep_sleep")
            for b in (0.01, 0.02) for w in (1e-5, 1e-6)}
    assert len(pols) == 8
    groups = W.group_policies(pols)
    assert len(groups) == 1 and len(groups[0]) == 8


# ---------------------------------------------------------------------------
# Equivalence: sweep == serial replay, per policy, all four kinds
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def swept(topo, pm):
    tr = _mini_trace(topo)
    return tr, W.sweep_policies(tr, topo, GRID, pm)


@pytest.mark.parametrize("name", list(GRID))
def test_sweep_matches_serial(swept, topo, pm, name):
    tr, results = swept
    serial, _ = S.simulate_trace(tr, topo, GRID[name], pm)
    got = results[name].as_dict()
    want = serial.as_dict()
    for k in CHECK_FIELDS:
        np.testing.assert_allclose(got[k], want[k], rtol=1e-9, atol=1e-12,
                                   err_msg=f"{name}.{k}")


def test_coalesce_parameter_curve_matches_serial(topo, pm):
    """A whole coalescing-window curve — max_delay x max_frames lanes —
    batches as ONE compiled replay of the coalesce static group, and every
    lane matches its own serial replay (the single-point coverage above
    never exercised these two knobs as vmapped curve axes)."""
    tr = _mini_trace(topo, n=10, seed=13)
    pols = {f"coal/{md:g}/{mf}": Policy(
                kind="coalesce", t_pdt=1e-5, t_dst=2e-4,
                max_delay=md, max_frames=mf,
                sleep_state="fast_wake", deep_state="deep_sleep")
            for md in (1e-5, 5e-5, 2e-4) for mf in (1, 4, 16)}
    assert len(W.group_policies(pols)) == 1        # one batched program
    got = W.sweep_policies(tr, topo, pols, pm)
    for name, pol in pols.items():
        want, _ = S.simulate_trace(tr, topo, pol, pm)
        for k in CHECK_FIELDS:
            np.testing.assert_allclose(
                got[name].as_dict()[k], want.as_dict()[k],
                rtol=1e-9, atol=1e-12, err_msg=f"{name}.{k}")
    # the knobs are live on the batch axis: deferral must move the
    # energy/latency numbers across the max_delay lanes once max_frames
    # allows coalescing...
    curve = {md: got[f"coal/{md:g}/16"].link_energy
             for md in (1e-5, 5e-5, 2e-4)}
    assert len(set(curve.values())) > 1, \
        f"max_delay lanes collapsed to one result: {curve}"
    # ...and a one-frame buffer (max_frames=1) disables deferral,
    # degenerating to the plain dual ladder exactly (DESIGN.md §6)
    dual, _ = S.simulate_trace(
        tr, topo, Policy(kind="dual", t_pdt=1e-5, t_dst=2e-4,
                         sleep_state="fast_wake", deep_state="deep_sleep"),
        pm)
    for md in (1e-5, 5e-5, 2e-4):
        for k in CHECK_FIELDS:
            np.testing.assert_allclose(
                got[f"coal/{md:g}/1"].as_dict()[k], dual.as_dict()[k],
                rtol=1e-12, err_msg=f"coal/{md:g}/1 vs dual: {k}")


def test_precoalesce_parameter_curve_matches_serial(topo, pm):
    """The hold-at-source window — hold_delay x hold_frames lanes — batches
    as ONE compiled replay of the precoalesce static group, every lane
    matches its own serial replay, the knobs are live on the batch axis,
    and a one-frame hold buffer degenerates to the plain dual ladder
    exactly (DESIGN.md §8)."""
    tr = _mini_trace(topo, n=10, seed=13)
    pols = {f"pre/{hd:g}/{hf}": Policy(
                kind="precoalesce", t_pdt=1e-5, t_dst=2e-4,
                hold_delay=hd, hold_frames=hf,
                sleep_state="fast_wake", deep_state="deep_sleep")
            for hd in (1e-5, 5e-5, 2e-4) for hf in (1, 4, 16)}
    assert len(W.group_policies(pols)) == 1        # one batched program
    got = W.sweep_policies(tr, topo, pols, pm)
    for name, pol in pols.items():
        want, _ = S.simulate_trace(tr, topo, pol, pm)
        for k in CHECK_FIELDS:
            np.testing.assert_allclose(
                got[name].as_dict()[k], want.as_dict()[k],
                rtol=1e-9, atol=1e-12, err_msg=f"{name}.{k}")
    curve = {hd: got[f"pre/{hd:g}/16"].link_energy
             for hd in (1e-5, 5e-5, 2e-4)}
    assert len(set(curve.values())) > 1, \
        f"hold_delay lanes collapsed to one result: {curve}"
    dual, _ = S.simulate_trace(
        tr, topo, Policy(kind="dual", t_pdt=1e-5, t_dst=2e-4,
                         sleep_state="fast_wake", deep_state="deep_sleep"),
        pm)
    for hd in (1e-5, 5e-5, 2e-4):
        for k in CHECK_FIELDS:
            np.testing.assert_allclose(
                got[f"pre/{hd:g}/1"].as_dict()[k], dual.as_dict()[k],
                rtol=1e-12, err_msg=f"pre/{hd:g}/1 vs dual: {k}")


def test_predict_parameter_curve_matches_serial(topo, pm):
    """The forecaster knobs — forecast_weight x forecast_margin lanes —
    batch as ONE compiled replay of the predict static group, every lane
    matches its own serial replay, and a zero-weight forecaster (EWMA off,
    every prediction falls back to the reactive timers) degenerates to the
    plain dual ladder exactly (DESIGN.md §8)."""
    tr = _mini_trace(topo, n=10, seed=13)
    pols = {f"pred/{fw:g}/{fm:g}": Policy(
                kind="predict", t_pdt=1e-5, t_dst=2e-4,
                forecast_weight=fw, forecast_margin=fm,
                sleep_state="fast_wake", deep_state="deep_sleep")
            for fw in (0.0, 0.5, 1.0) for fm in (1.0, 4.0)}
    assert len(W.group_policies(pols)) == 1        # one batched program
    got = W.sweep_policies(tr, topo, pols, pm)
    for name, pol in pols.items():
        want, _ = S.simulate_trace(tr, topo, pol, pm)
        for k in CHECK_FIELDS:
            np.testing.assert_allclose(
                got[name].as_dict()[k], want.as_dict()[k],
                rtol=1e-9, atol=1e-12, err_msg=f"{name}.{k}")
    dual, _ = S.simulate_trace(
        tr, topo, Policy(kind="dual", t_pdt=1e-5, t_dst=2e-4,
                         sleep_state="fast_wake", deep_state="deep_sleep"),
        pm)
    for fm in (1.0, 4.0):
        for k in CHECK_FIELDS:
            np.testing.assert_allclose(
                got[f"pred/0/{fm:g}"].as_dict()[k], dual.as_dict()[k],
                rtol=1e-12, err_msg=f"pred/0/{fm:g} vs dual: {k}")


def test_sweep_max_group_split_matches(topo, pm):
    """Splitting a group into sub-batches must not change results."""
    tr = _mini_trace(topo, n=8, seed=5)
    pols = {f"pb{b:g}": Policy(kind="perfbound", bound=b)
            for b in (0.01, 0.02, 0.03, 0.05)}
    full = W.sweep_policies(tr, topo, pols, pm)
    split = W.sweep_policies(tr, topo, pols, pm, max_group=1)
    for name in pols:
        np.testing.assert_allclose(
            [full[name].as_dict()[k] for k in CHECK_FIELDS],
            [split[name].as_dict()[k] for k in CHECK_FIELDS], rtol=1e-12)


def test_compare_policies_rides_sweep(topo, pm):
    """The §4 protocol wrapper produces the same table as serial runs."""
    tr = _mini_trace(topo, n=8, seed=7)
    pols = {"fixed": Policy(kind="fixed", t_pdt=1e-4,
                            sleep_state="deep_sleep"),
            "pbc": Policy(kind="perfbound_correct", bound=0.01)}
    out = S.compare_policies(tr, topo, pols, pm)
    base, _ = S.simulate_trace(tr, topo, Policy(kind="none"), pm)
    assert out["baseline"]["exec_overhead_pct"] == 0.0
    np.testing.assert_allclose(out["baseline"]["makespan"], base.makespan,
                               rtol=1e-12)
    for name, pol in pols.items():
        r, _ = S.simulate_trace(tr, topo, pol, pm)
        np.testing.assert_allclose(out[name]["makespan"], r.makespan,
                                   rtol=1e-9)
        np.testing.assert_allclose(
            out[name]["exec_overhead_pct"],
            100 * (r.makespan / base.makespan - 1), rtol=1e-6, atol=1e-9)


def test_sweep_handles_baseline_name_collision(topo, pm):
    tr = _mini_trace(topo, n=4, seed=11)
    out = S.compare_policies(
        tr, topo, {"__baseline__": Policy(kind="fixed", t_pdt=1e-4)}, pm)
    assert "baseline" in out and "__baseline__" in out
    assert out["__baseline__"]["makespan"] >= out["baseline"]["makespan"]


# ---------------------------------------------------------------------------
# Energy-accounting invariants (issue satellite): every second of every
# link's timeline lands at exactly one power level
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", ["none", "fixed/ds/100us", "fixed/ds/0",
                                  "pb/ds/1pct", "pbc/ds/1pct", "dual/fast",
                                  "coal/on", "pbd/1pct"])
def test_close_out_accounts_full_makespan(topo, pm, name):
    """After close_out, time_wake + time_sleep ≈ makespan on every link
    (overshoot only, bounded by the wake/sleep transition extensions)."""
    pol = GRID[name]
    tr = _mini_trace(topo)
    res, _ = S.simulate_trace(tr, topo, pol, pm)

    # replay the same chunks to get the final net state for close_out
    net = S.init_net(topo.n_links, pol)
    ready = np.zeros(topo.n_nodes)
    for step in tr.steps:
        if step.compute_nodes is not None and len(step.compute_nodes):
            ready[step.compute_nodes] += step.compute_secs
        if step.msgs is not None and len(step.msgs):
            src, dst = step.msgs[:, 0], step.msgs[:, 1]
            nbytes = step.msgs[:, 2].astype(np.float64)
            t_inj = ready[src]
            order = np.argsort(t_inj, kind="stable")
            links, dirs, nhops = topo.routes(src[order], dst[order])
            msgs = S._pad_msgs(links, dirs, nhops, t_inj[order],
                               nbytes[order])
            net, out = S.sim_chunk(net, msgs, pol, pm, topo.n_links)
            np.maximum.at(ready, dst[order],
                          np.asarray(out[0])[:len(src)])
        if step.barrier:
            ready[tr.nodes] = ready[tr.nodes].max()

    t_end = float(ready[tr.nodes].max())
    np.testing.assert_allclose(t_end, res.makespan, rtol=1e-12)
    tw, ts, ts2 = (np.asarray(x) for x in
                   S.close_out(net, t_end, pol, topo.n_links))
    assert (tw >= -1e-12).all() and (ts >= -1e-12).all() \
        and (ts2 >= -1e-12).all()
    over = (tw + ts + ts2) - max(t_end, float(net["last_end"]
                                              [:topo.n_links].max()))
    assert (over > -1e-9).all(), "undershoot: unaccounted link time"
    bound = np.asarray(net["n_wake"][:topo.n_links]) * \
        (pol.state.t_w + pol.sync_overhead + pol.state.t_s) + \
        np.asarray(net["n_deep"][:topo.n_links]) * \
        (pol.deep.t_w + pol.sync_overhead + pol.deep.t_s) + 1e-9
    assert (over <= bound).all(), "overshoot beyond transition extensions"


# ---------------------------------------------------------------------------
# Lane reduction on the device (simulator.lane_sums) against numpy float64
# ---------------------------------------------------------------------------


def _np_lane_sums(net, t_end, pol, n):
    """One lane's LANE_SUMS from its host net state, the close-out written
    as the span after the last transmission less its two sleep spans."""
    last, dl, dl2 = (net[k][:n] for k in ("last_end", "deadline",
                                           "deadline2"))
    t = max(t_end, last.max())
    with np.errstate(invalid="ignore"):
        s1 = dl + pol.state.t_s           # row-1 sleep starts
        s2 = dl2 + pol.deep.t_s           # row-2 sleep starts
        sleep1 = np.where(s1 < t, np.minimum(t, dl2) - s1, 0.0)
        sleep2 = np.where(s2 < t, t - s2, 0.0)
    wake = (t - last) - sleep1 - sleep2
    return np.array(
        [(net["time_wake"][:n] + wake).sum(),
         (net["time_sleep"][:n] + sleep1).sum(),
         (net["time_sleep2"][:n] + sleep2).sum(), last.max()]
        + [net[k][:n].sum() for k in ("n_wake", "n_hit", "n_miss",
                                      "n_deep")], np.float64)


@pytest.mark.parametrize("kind", sorted({p.kind for p in GRID.values()}))
def test_lane_sums_match_numpy(topo, pm, kind):
    """Every lane of every static group of a kind reduces, on the device,
    to what numpy computes from the same full net state."""
    import jax

    from repro.core import replay
    from repro.traffic.plan import compile_plan
    pols = {n: p for n, p in GRID.items() if p.kind == kind}
    plan = compile_plan(_mini_trace(topo), topo)
    for names in W.group_policies(pols):
        group = [pols[n] for n in names]
        nets, t_end, _, _, _ = replay.replay_plan(plan, group, pm)
        got = S.lane_sums(nets, t_end, group, topo.n_links)
        assert got.shape == (len(group), len(S.LANE_SUMS))
        host = jax.tree.map(np.asarray, nets)
        for b, pol in enumerate(group):
            lane = {k: v[b] for k, v in host.items() if k != "pred"}
            np.testing.assert_allclose(
                got[b], _np_lane_sums(lane, t_end[b], pol, topo.n_links),
                rtol=1e-12, err_msg=names[b])


def test_sweep_cells_reads_back_only_lane_sums(topo, pm):
    """A (traces x policies) grid brings to the host each lane's end time,
    latency sums and LANE_SUMS row, and no array of its net state."""
    from repro.core.instrument import span_log
    traces = {"a": _mini_trace(topo, seed=3), "b": _mini_trace(topo, seed=5)}
    pols = {n: GRID[n] for n in ("none", "fixed/fw/10us", "fixed/ds/100us",
                                 "pb/ds/1pct")}
    with span_log() as log:
        out = W.sweep_scenarios(traces, topo, pols, pm)
    lanes = len(traces) * len(pols)
    assert sum(map(len, out.values())) == lanes
    assert log.counts["readback_bytes"] == 8 * lanes * (3 + len(S.LANE_SUMS))
    assert log.counts["readback_bytes"] < 8 * (topo.n_links + 1)


def test_asleep_frac_in_unit_interval(swept):
    _, results = swept
    for name, res in results.items():
        assert 0.0 <= res.asleep_frac <= 1.0, name
        assert res.hits >= 0 and res.misses >= 0
        assert res.n_wake_transitions == res.misses, name


def test_none_policy_never_sleeps(swept, topo, pm):
    _, results = swept
    res = results["none"]
    assert res.asleep_frac == 0.0
    assert res.n_wake_transitions == 0
    assert res.misses == 0
    # link energy is exactly every port at wake power for the whole run
    want = 2 * pm.port_power * topo.n_links * res.makespan
    np.testing.assert_allclose(res.link_energy, want, rtol=1e-9)
