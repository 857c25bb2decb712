"""Frozen copies of the drifting arrival streams and of the streaming
advisor's switching controller, and the advisor's loop written plainly on
the host step-loop reference.

Each advisor window is synthesized from ``(seed, window)`` alone, replayed
under the always-on baseline and every pool policy on
``refsim.sim.simulate_trace_reference``, reported relative to the
window's baseline, and folded into the hysteresis controller, which picks
the next window's incumbent.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Dict, Tuple

import numpy as np

from refsim.sim import relative_rows, simulate_trace_reference
from refsim.traffic import Trace, _flow_sizes, _pairs, allocate

BASELINE = "baseline"


DRIFT_KINDS = ("diurnal", "flash", "regimes")

# Philox stream tags: rate/regime path vs per-window flow sampling.
_TAG_PATH = 0xD21F7
_TAG_WINDOW = 0x51A7E


def _rng(*key) -> np.random.Generator:
    """Counter-based Philox keyed on an int tuple (via SeedSequence) —
    platform-stable, and independent per (seed, window) so any window
    re-synthesizes bit-identically without replaying the stream prefix."""
    return np.random.Generator(np.random.Philox([int(k) for k in key]))


@dataclasses.dataclass(frozen=True)
class DriftSpec:
    """One named drifting workload stream (a drift-catalog entry).

    ``windows`` advisor windows x ``steps`` service sub-windows; the
    switching controller makes one decision per window.  ``params`` holds
    the drift-kind knobs as sorted (key, value) pairs (``params_of``).
    """
    name: str
    drift: str                    # diurnal | flash | regimes
    n_nodes: int = 16
    seed: int = 0
    windows: int = 24             # advisor windows (controller decisions)
    steps: int = 8                # service sub-windows per advisor window
    window_secs: float = 5e-3     # compute advance per sub-window
    mean_bytes: int = 32 << 10
    max_flows: int = 64           # one-bucket plan-shape guarantee
    jitter: float = 0.5
    mapping: str = "linear"
    family: str = "dc"            # catalog family the challenger pool taps
    params: tuple = ()            # drift knobs, see params_of
    description: str = ""

    def __post_init__(self):
        if self.drift not in DRIFT_KINDS:
            raise ValueError(f"drift kind {self.drift!r} not in "
                             f"{DRIFT_KINDS}")
        if self.n_nodes < 2 or self.windows < 1 or self.steps < 1:
            raise ValueError(f"degenerate drift spec: n_nodes="
                             f"{self.n_nodes} windows={self.windows} "
                             f"steps={self.steps}")
        if not 2 <= self.max_flows <= 64:
            raise ValueError(f"max_flows must be in [2, 64] (one message "
                             f"bucket), got {self.max_flows}")

    def opt(self, key: str, default):
        return dict(self.params).get(key, default)

    def scaled(self, n_nodes: int | None = None, windows: int | None = None,
               seed: int | None = None) -> "DriftSpec":
        """The same stream on a different allocation / length / seed."""
        return dataclasses.replace(
            self,
            n_nodes=self.n_nodes if n_nodes is None else n_nodes,
            windows=self.windows if windows is None else windows,
            seed=self.seed if seed is None else seed)


# ---------------------------------------------------------------------------
# Rate paths
# ---------------------------------------------------------------------------


def _rates_diurnal(spec: DriftSpec) -> np.ndarray:
    base = spec.opt("base_rate", 2000.0)
    amp = spec.opt("amp", 0.9)
    period = spec.opt("period", 12.0)          # in advisor windows
    g = np.arange(spec.windows * spec.steps, dtype=np.float64)
    phase = 2 * np.pi * g / (period * spec.steps)
    # open at the trough: the stream starts in the quiet night phase
    rate = base * (1 + amp * np.sin(phase - np.pi / 2))
    return np.maximum(rate, spec.opt("floor", 1.0))


def _rates_flash(spec: DriftSpec) -> np.ndarray:
    base = spec.opt("base_rate", 400.0)
    mult = spec.opt("spike_mult", 12.0)
    spike_every = spec.opt("spike_every", 6.0)  # mean windows between spikes
    spike_len = int(spec.opt("spike_len", spec.steps))   # sub-windows
    n = spec.windows * spec.steps
    r = _rng(spec.seed, _TAG_PATH)
    p = 1.0 / max(spike_every * spec.steps, 1.0)
    starts = r.random(n) < p
    spike = np.zeros(n, bool)
    for i in np.nonzero(starts)[0]:
        spike[i:i + spike_len] = True
    return np.where(spike, base * mult, base)


def _rates_regimes(spec: DriftSpec) -> np.ndarray:
    lo = spec.opt("rate_lo", 120.0)
    hi = spec.opt("rate_hi", 6000.0)
    path = regime_path(spec)
    per_window = np.where(path, hi, lo)
    return np.repeat(per_window, spec.steps).astype(np.float64)


def regime_path(spec: DriftSpec) -> np.ndarray:
    """(windows,) bool busy-regime path of a ``regimes`` drift — aligned to
    advisor-window boundaries, so hysteresis tests can bound the switch
    count by the number of regime changes.  Non-regime drifts report the
    per-window above-median mask (a coarse busy indicator)."""
    if spec.drift != "regimes":
        rates = window_rates(spec).mean(axis=1)
        return rates > np.median(rates)
    p_stay = spec.opt("p_stay", 0.85)
    p_busy0 = spec.opt("p_busy0", 0.0)
    r = _rng(spec.seed, _TAG_PATH)
    path = np.zeros(spec.windows, bool)
    busy = bool(r.random() < p_busy0)
    for w in range(spec.windows):
        path[w] = busy
        busy = bool(r.random() < (p_stay if busy else 1 - p_stay))
    return path


_RATE_FNS = {"diurnal": _rates_diurnal, "flash": _rates_flash,
             "regimes": _rates_regimes}


def window_rates(spec: DriftSpec) -> np.ndarray:
    """(windows, steps) per-sub-window arrival rates (flows/s) — a pure
    deterministic function of the spec, shared by synthesis, the timeline
    report and the drift tests."""
    rates = _RATE_FNS[spec.drift](spec)
    return rates.reshape(spec.windows, spec.steps)


# ---------------------------------------------------------------------------
# Window synthesis
# ---------------------------------------------------------------------------


def window_trace(spec: DriftSpec, topo, w: int) -> Trace:
    """Synthesize (or fetch the cached) Trace of advisor window ``w``.

    Structure per sub-window: one jittered compute step then one message
    step of ``clip(Poisson(rate x window_secs), 2, max_flows)`` flows
    between uniform src != dst pairs with heavy-tailed sizes; barrier on
    the window's last sub-window (windows end synchronized, so each
    replays from clean clocks exactly like a standalone trace).
    """
    if not 0 <= w < spec.windows:
        raise IndexError(f"window {w} outside stream [0, {spec.windows})")
    rates = window_rates(spec)[w]
    nodes = allocate(topo, spec.n_nodes, spec.mapping, spec.seed)
    r = _rng(spec.seed, _TAG_WINDOW, w)
    t = Trace(nodes=nodes, name=f"{spec.name}/w{w:04d}")
    for k in range(spec.steps):
        t.compute(r.uniform(1 - spec.jitter, 1 + spec.jitter, spec.n_nodes)
                  * spec.window_secs)
        # floor of 2 live flows: keeps every window's needs_sort flag (and
        # with it the compiled program key) independent of the drawn rates
        m = int(np.clip(r.poisson(rates[k] * spec.window_secs), 2,
                        spec.max_flows))
        src, dst = _pairs(r, nodes, m)
        t.messages(np.stack([src, dst, _flow_sizes(r, m, spec.mean_bytes)],
                            axis=1), barrier=k == spec.steps - 1)
    return t


WindowScores = Dict[str, Tuple[float, float]]   # name -> (degradation%, energy)


@dataclass(frozen=True)
class SwitchConfig:
    """Hysteresis knobs of the streaming advisor."""
    budget_pct: float = 1.0     # max smoothed exec overhead vs baseline, %
    margin_pct: float = 5.0     # challenger must beat incumbent energy by
    min_dwell: int = 2          # windows between switches
    smooth: float = 0.5         # EWMA weight of the newest window (1 = raw)

    def __post_init__(self):
        assert self.budget_pct >= 0 and self.margin_pct >= 0
        assert self.min_dwell >= 1 and 0 < self.smooth <= 1


@dataclass
class ControllerState:
    """Mutable-through-``decide`` controller state (one per stream)."""
    incumbent: str
    dwell: int = 0               # windows since the last switch
    switches: int = 0
    ewma: Dict[str, Tuple[float, float]] = field(default_factory=dict)

    def feasible(self, budget_pct: float) -> Dict[str, float]:
        """{name: smoothed energy} of budget-respecting candidates."""
        return {n: e for n, (d, e) in self.ewma.items() if d <= budget_pct}


def _smooth(state: ControllerState, scores: WindowScores, alpha: float):
    for name, (d, e) in scores.items():
        pd, pe = state.ewma.get(name, (d, e))
        state.ewma[name] = (alpha * d + (1 - alpha) * pd,
                            alpha * e + (1 - alpha) * pe)


def decide(state: ControllerState, scores: WindowScores,
           cfg: SwitchConfig) -> Tuple[ControllerState, bool, str]:
    """Fold one window's scores into ``state`` and decide the NEXT window's
    incumbent.  Returns ``(state, switched, reason)``; ``state`` is the
    same object, updated in place (EWMAs, dwell, switch count).

    ``scores`` maps each candidate (incumbent + challengers + baseline) to
    its ``(degradation_pct, energy)`` on the window just replayed —
    degradation vs the window's own always-on baseline, energy the
    windowed objective (lower is better).
    """
    assert state.incumbent in scores, \
        f"incumbent {state.incumbent!r} missing from window scores"
    _smooth(state, scores, cfg.smooth)
    state.dwell += 1

    feasible = state.feasible(cfg.budget_pct)
    inc_d, inc_e = state.ewma[state.incumbent]
    inc_feasible = state.incumbent in feasible
    if not feasible or state.dwell < cfg.min_dwell:
        return state, False, "dwell" if feasible else "no-feasible"

    best = min(feasible, key=lambda n: (feasible[n], n))
    if best == state.incumbent:
        return state, False, "incumbent-best"
    if inc_feasible and feasible[best] > inc_e * (1 - cfg.margin_pct / 100):
        return state, False, "margin"

    reason = "over-budget" if not inc_feasible else "margin-beaten"
    state.incumbent = best
    state.dwell = 0
    state.switches += 1
    return state, True, reason


def _window_scores(rows: dict, objective: str) -> Dict[str, tuple]:
    return {name: (row["exec_overhead_pct"], row[objective])
            for name, row in rows.items()}


def advise_stream(spec: DriftSpec, topo, pool: dict, pm, budget_pct: float,
                  margin_pct: float, min_dwell: int, smooth: float,
                  objective: str, base_policy) -> dict:
    """The advisor's per-window timeline and stream totals."""
    cfg = SwitchConfig(budget_pct=budget_pct, margin_pct=margin_pct,
                       min_dwell=min_dwell, smooth=smooth)
    state = ControllerState(incumbent=next(iter(pool)))
    timeline = []
    totals = {n: {"energy": 0.0, "makespan": 0.0} for n in (BASELINE, *pool)}
    online = {"energy": 0.0, "makespan": 0.0}
    for w in range(spec.windows):
        trace = window_trace(spec, topo, w)
        base, _ = simulate_trace_reference(trace, topo, base_policy, pm)
        res = {n: simulate_trace_reference(trace, topo, p, pm)[0]
               for n, p in pool.items()}
        rows = relative_rows(base, res, BASELINE)
        served = state.incumbent
        for name in totals:
            totals[name]["energy"] += rows[name][objective]
            totals[name]["makespan"] += rows[name]["makespan"]
        online["energy"] += rows[served][objective]
        online["makespan"] += rows[served]["makespan"]
        state, switched, reason = decide(
            state, _window_scores(rows, objective), cfg)
        timeline.append({
            "window": w, "incumbent": served,
            "overhead_pct": rows[served]["exec_overhead_pct"],
            "energy": rows[served][objective],
            "saved_pct": 100 * (1 - rows[served][objective]
                                / rows[BASELINE][objective])
            if rows[BASELINE][objective] else 0.0,
            "switched": switched, "reason": reason,
            "next_incumbent": state.incumbent})
    return {"timeline": timeline, "switches": state.switches,
            "final_incumbent": state.incumbent,
            "baseline_energy": totals[BASELINE]["energy"],
            "online_energy": online["energy"]}
