"""The harness on the CPU, at a size a test run holds.

Each test builds a checkout of its own: ``BENCHMARK.json`` and a copy of
``bench/`` whose configurations are cut to a small Megafly or fat-tree
and 16-node jobs.  The harness's look for a chip is skipped; the rest of
a run is driven as ``run.py`` drives it.
"""
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import jax
import pytest

import harness
import surface_util

HERE = Path(__file__).resolve().parent
REPO = HERE.parent
LAMMPS = "perfbound-lammps-megafly4160"
DC = "grid-dc-fattree-k26"
STREAM = "stream-diurnal-megafly4160"
GRID = (LAMMPS, DC)
SMALL = {"megafly4160": {"n_groups": 5, "leaves_per_group": 4,
                         "spines_per_group": 4, "nodes_per_leaf": 4},
         "fattree-k26": {"k": 4}}


def add_grid_cells(b: dict):
    """The issue's two grid cells, left out of ``BENCHMARK.json`` for a
    fault of the program on the chip (on the CPU the program is exact),
    with their configuration and metrics: each returns by entries alone."""
    b["configs"].append({"name": "fattree-k26",
                         "source": "https://doi.org/10.1145/1402958.1402967",
                         "file": "bench/configs/fattree-k26.json",
                         "reduced": [], "why": "the k=26 fat-tree"})
    b["workloads"] += [
        {"name": LAMMPS, "config": "megafly4160",
         "traffic": "perfbound-lammps", "chips": 1,
         "why": "left out on the chip for a fault of the program"},
        {"name": DC, "config": "fattree-k26", "traffic": "grid-dc",
         "chips": 1, "why": "left out on the chip for a fault of the program"}]
    b["end_to_end"].append({"name": "grid_hops_per_s", "unit": "hops/s",
                            "better": "higher", "bound": 0.25,
                            "source": "host_clock",
                            "workloads": list(GRID)})
    for name, unit, layer, source in (
            ("host_prep_ms.grid", "ms", "trace synthesis and plan lowering",
             "host_clock"),
            ("device_busy_ms.grid", "ms", "device replay", "device_trace"),
            ("device_idle_pct.grid", "%", "device", "device_trace")):
        b["per_layer"].append({"name": name, "unit": unit, "better": "lower",
                               "source": source, "layer": layer,
                               "moves": "grid_hops_per_s",
                               "workloads": list(GRID)})
    for m in b["per_layer"]:
        if m["moves"] == "setup_s":
            m["workloads"] += list(GRID)


def small_checkout(root: Path) -> Path:
    """A checkout cut to small sizes, whose ``BENCHMARK.json`` also names
    the grid cells the benchmark leaves out."""
    b = json.loads((REPO / "BENCHMARK.json").read_text())
    add_grid_cells(b)
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    shutil.copytree(HERE, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "test_*"))
    for name, params in SMALL.items():
        p = root / "bench" / "configs" / f"{name}.json"
        c = json.loads(p.read_text())
        c["topology"]["params"], c["n_nodes"] = params, 16
        p.write_text(json.dumps(c))
    p = root / "bench" / "traffic" / "perfbound-lammps.json"
    t = json.loads(p.read_text())
    t["scenarios"][0]["params"]["iters"] = 2
    t["policies"] = {k: v for k, v in t["policies"].items()
                     if k in ("pb-1pct-ds", "pbc-5pct-fw")}
    p.write_text(json.dumps(t))
    p = root / "bench" / "traffic" / "grid-dc.json"
    t = json.loads(p.read_text())
    t["scenarios"] = [s for s in t["scenarios"]
                      if s["name"] in ("dc-onoff", "dc-incast")]
    t["policies"] = {k: v for k, v in t["policies"].items()
                     if k in ("fixed-fw-10us", "coalesce-50us",
                              "predict-ewma")}
    p.write_text(json.dumps(t))
    p = root / "bench" / "traffic" / "stream-diurnal.json"
    t = json.loads(p.read_text())
    t["drift"]["windows"] = 4
    p.write_text(json.dumps(t))
    return root


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return small_checkout(tmp_path_factory.mktemp("checkout"))


def run(root, workload, seed=2 ** 31 + 77, seconds=0.2, trace=False):
    cell = harness.load_cell(root, workload)
    return harness.run_cell(cell, seed, seconds, trace, jax.devices(),
                            time.perf_counter())


E2E = {LAMMPS: "grid_hops_per_s", DC: "grid_hops_per_s",
       STREAM: "readvise_ms_p95"}
LAYERS = {DC: {"host_prep_ms.grid", "device_busy_ms.grid",
               "device_idle_pct.grid", "compile_s.setup",
               "programs_compiled.setup"},
          STREAM: {"device_busy_ms.stream", "device_idle_pct.stream",
                   "compile_s.setup", "programs_compiled.setup"}}


@pytest.mark.parametrize("workload", [LAMMPS, DC, STREAM])
def test_small_cell_is_correct(checkout, workload):
    out = run(checkout, workload)
    assert out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] >= 1
    assert set(out["metrics"]) == {"setup_s", E2E[workload]}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert list(out)[-1] == "checks"
    assert {"rel_gap", "pct_gap", "missing"} <= set(out["checks"])
    assert out["device"]["count"] == len(jax.devices())


@pytest.mark.parametrize("workload", [DC, STREAM])
def test_traced_run_reports_the_layers(checkout, workload):
    out = run(checkout, workload, trace=True)
    assert out["correct"] is True
    assert set(out["metrics"]) == LAYERS[workload]
    dev = out["device"]
    assert 0 < dev["busy_s"] <= dev["window_s"]
    idle = [v["value"] for k, v in out["metrics"].items() if "idle" in k]
    assert idle and all(0 <= v < 100 for v in idle)
    assert out["breakdown"]["device_ops"] and out["breakdown"]["idle_gaps"]
    assert len(out["breakdown"]["device_ops"]) <= 10


def test_hops_are_counted_outside_the_timed_question(checkout, monkeypatch):
    """A grid question times only the program's calls: its hops are
    counted after the window, on the frozen reference's traces."""
    import metric_math
    calls = []
    count = metric_math.trace_hops

    def trace_hops(trace, topo):
        calls.append(trace)
        return count(trace, topo)
    monkeypatch.setattr(metric_math, "trace_hops", trace_hops)
    cell = harness.load_cell(checkout, DC)
    surface = harness.load_module(checkout / "bench/surfaces/suite.py", "sq")
    state = surface.prepare(cell.config, cell.traffic)
    q = harness.ask(surface, state, 99, 0)
    assert "hops" not in q and not calls
    surface.tally(state, q)
    lanes = len(cell.traffic["policies"]) + 1
    assert len(calls) == len(cell.traffic["scenarios"])
    assert q["hops"] == lanes * sum(count(t, state["ref_topo"])
                                    for t in calls) > 0


def test_cell_and_metric_added_by_files_alone(tmp_path):
    """A new traffic mix, a new cell and a new per-layer metric need new
    files and new entries, and no edit of a file the harness has."""
    root = small_checkout(tmp_path)
    t = json.loads((root / "bench/traffic/grid-dc.json").read_text())
    t["scenarios"] = [s for s in t["scenarios"] if s["name"] == "dc-incast"]
    t["policies"] = {"fixed-fw-10us": t["policies"]["fixed-fw-10us"]}
    (root / "bench/traffic/incast-fixed.json").write_text(json.dumps(t))
    (root / "bench/metrics/questions.grid.py").write_text(
        "def read(run):\n    return len(run.questions) or None\n")
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["workloads"].append({"name": "incast-fixed-megafly4160",
                           "config": "megafly4160",
                           "traffic": "incast-fixed", "chips": 1,
                           "why": "a cell added by files alone"})
    b["per_layer"].append({"name": "questions.grid", "unit": "questions",
                           "better": "higher", "source": "host_clock",
                           "layer": "entry points",
                           "moves": "grid_hops_per_s",
                           "workloads": ["incast-fixed-megafly4160"]})
    for m in b["end_to_end"]:
        if m["name"] == "grid_hops_per_s":
            m["workloads"].append("incast-fixed-megafly4160")
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    out = run(root, "incast-fixed-megafly4160", trace=True)
    assert out["correct"] is True
    assert out["metrics"]["questions.grid"]["value"] >= 1
    out = run(root, "incast-fixed-megafly4160")
    assert set(out["metrics"]) == {"setup_s", "grid_hops_per_s"}


def test_benchmark_names_every_file_it_needs():
    """Each cell's configuration, traffic, surface and metric readers are
    files under ``bench/``; each configuration is used by a cell."""
    b = json.loads((REPO / "BENCHMARK.json").read_text())
    configs = {c["name"]: c for c in b["configs"]}
    cells = {w["name"] for w in b["workloads"]}
    assert {w["config"] for w in b["workloads"]} == set(configs)
    for c in configs.values():
        assert (REPO / c["file"]).is_file()
    for w in b["workloads"]:
        t = json.loads((HERE / "traffic" / f"{w['traffic']}.json")
                       .read_text())
        assert (HERE / "surfaces" / f"{t['surface']}.py").is_file()
    for m in b["end_to_end"] + b["per_layer"]:
        assert (HERE / "metrics" / f"{m['name']}.py").is_file()
        assert set(m.get("workloads", cells)) <= cells
    for cell in cells:
        e2e = [m["name"] for m in b["end_to_end"]
               if cell in m.get("workloads", cells)]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert any(m["moves"] in e2e for m in b["per_layer"]
                   if cell in m.get("workloads", cells))


@pytest.mark.parametrize("workload", [LAMMPS, DC, STREAM])
def test_control_in_float32_is_not_correct(checkout, workload):
    """The reference in the precision below the configuration's (float32
    for float64), put in the program's place, fails the limits."""
    cell = harness.load_cell(checkout, workload)
    surface = harness.load_module(
        HERE / "surfaces" / f"{cell.traffic['surface']}.py", "sfc")
    state = surface.prepare(cell.config, cell.traffic)
    limits = cell.traffic["check"]["limits"]
    gaps = surface_util.control(surface, state, 5)
    assert any(gaps[k] > limits[k] for k in limits), gaps


def _still(batch, proto, params, pm, carry):
    """A replay whose every segment returns the state it was given."""
    from repro.core import replay
    for seg in batch.segments:
        md, ns = replay._seg_flags(seg, proto)
        replay._multi_segment_runner(proto, pm, batch.n_links, seg.cap,
                                     md, ns)
    nets, ready, lat_sum, lat_max = carry
    return (nets, replay._participant_max_multi(batch.part_mask, ready),
            lat_sum, lat_max)


def _half(orig):
    """Plans of traces with every other message of each step left out."""
    import copy

    def compile_plan(trace, topo, *a, **kw):
        tr = copy.deepcopy(trace)
        for st in tr.steps:
            if st.msgs is not None and len(st.msgs) > 1:
                st.msgs = st.msgs[::2]
        return orig(tr, topo, *a, **kw)
    return compile_plan


def _altered(orig):
    """Rows whose link energy is off by one part in a million."""
    import dataclasses

    def summarize(*a, **kw):
        r = orig(*a, **kw)
        return dataclasses.replace(r, link_energy=r.link_energy * (1 + 1e-6))
    return summarize


@pytest.mark.parametrize("workload", [LAMMPS, DC, STREAM])
@pytest.mark.parametrize("fault", ["state_unchanged", "half_the_batch",
                                   "answer_altered"])
def test_fault_in_timed_path_is_not_correct(checkout, monkeypatch, fault,
                                            workload):
    from repro.core import replay, simulator, sweep
    if fault == "state_unchanged":
        monkeypatch.setattr(replay, "run_segments_multi", _still)
    elif fault == "half_the_batch":
        monkeypatch.setattr(sweep, "compile_plan",
                            _half(sweep.compile_plan))
    else:
        monkeypatch.setattr(simulator, "summarize",
                            _altered(simulator.summarize))
    out = run(checkout, workload, seed=1234 + len(fault))
    assert out["correct"] is False
    assert any(c["value"] > c["limit"] for c in out["checks"].values())


def test_run_without_a_tpu_fails_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(HERE / "run.py"), "--workload", STREAM,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert "{" not in p.stdout


def test_unknown_workload_fails(tmp_path):
    root = small_checkout(tmp_path)
    with pytest.raises(harness.CellError):
        harness.load_cell(root, "no-such-cell")
