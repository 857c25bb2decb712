"""The PerfBound stream cell on the CPU, at the harness tests' small size:
a k=4 fat-tree, 16-node jobs, and the cell's traffic cut here to 4
advisor windows."""
import json

import jax
import pytest

import harness
import surface_util
from test_bench_harness import (HERE, _altered, _half, _still, run,
                                small_checkout)

CELL = "stream-diurnal-perfbound-fattree-k26"
TRAFFIC = "stream-diurnal-perfbound"


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    root = small_checkout(tmp_path_factory.mktemp("checkout"))
    p = root / "bench" / "traffic" / f"{TRAFFIC}.json"
    t = json.loads(p.read_text())
    t["drift"]["windows"] = 4
    p.write_text(json.dumps(t))
    return root


def test_pool_is_the_papers_perfbound_variants():
    """The traffic is ``stream-diurnal``'s but for the pool, and its limits
    are no looser."""
    t = json.loads((HERE / "traffic" / f"{TRAFFIC}.json").read_text())
    base = json.loads((HERE / "traffic" / "stream-diurnal.json").read_text())
    assert {k: v for k, v in t.items() if k not in ("pool", "why")} \
        == {k: v for k, v in base.items() if k not in ("pool", "why")}
    assert sorted(p["kind"] for p in t["pool"].values()) == [
        "perfbound", "perfbound_correct", "perfbound_dual"]
    assert all(p["hist_mode"] == "keep_all" and p["bound"] == 0.01
               for p in t["pool"].values())


def test_small_cell_is_correct(checkout):
    out = run(checkout, CELL)
    assert out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] >= 1
    assert set(out["metrics"]) == {"setup_s", "readvise_ms_p95"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert all(c["value"] <= c["limit"] for c in out["checks"].values())
    assert set(out["checks"]) == {"decisions", "rel_gap", "pct_gap",
                                  "missing"}


def test_control_in_float32_is_not_correct(checkout):
    cell = harness.load_cell(checkout, CELL)
    surface = harness.load_module(HERE / "surfaces" / "stream.py", "sfc")
    state = surface.prepare(cell.config, cell.traffic)
    limits = cell.traffic["check"]["limits"]
    gaps = surface_util.control(surface, state, 5)
    assert any(gaps[k] > limits[k] for k in limits), gaps


@pytest.mark.parametrize("fault", ["state_unchanged", "half_the_batch",
                                   "answer_altered"])
def test_fault_in_timed_path_is_not_correct(checkout, monkeypatch, fault):
    from repro.core import replay, simulator, sweep
    if fault == "state_unchanged":
        monkeypatch.setattr(replay, "run_segments_multi", _still)
    elif fault == "half_the_batch":
        monkeypatch.setattr(sweep, "compile_plan",
                            _half(sweep.compile_plan))
    else:
        monkeypatch.setattr(simulator, "summarize",
                            _altered(simulator.summarize))
    out = run(checkout, CELL, seed=4321 + len(fault))
    assert out["correct"] is False
    assert any(c["value"] > c["limit"] for c in out["checks"].values())


def test_traced_run_reports_lane_state_bytes(checkout):
    """Every ``.stream`` layer the cell lists, and the lanes' initial
    state: three PerfBound lanes' histograms dwarf the baseline's."""
    out = run(checkout, CELL, trace=True)
    assert out["correct"] is True
    cell = harness.load_cell(checkout, CELL)
    listed = {m["name"] for m in cell.per_layer}
    assert "lane_state_bytes.stream" in listed
    assert listed - set(out["metrics"]) <= {"replay_device_ms.stream"}
    from repro.topology.fattree import FatTree
    rows = FatTree(k=4).n_links + 1          # links and the dummy row
    hist = 2 * 8 * rows * 200                # counts and sums, float64
    assert out["metrics"]["lane_state_bytes.stream"]["value"] > 3 * hist
    assert out["device"]["count"] == len(jax.devices())
