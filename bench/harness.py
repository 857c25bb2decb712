"""The benchmark harness: one cell of ``BENCHMARK.json``, one process.

Everything that belongs to one configuration, one traffic mix or one
metric is a file of its own, found by the name ``BENCHMARK.json`` gives:

* ``bench/configs/<config>.json`` — the fabric, the power model and the
  allocation size (the configuration's ``file``);
* ``bench/traffic/<traffic>.json`` — the question a cell repeats: which
  surface asks it (``bench/surfaces/<surface>.py``), its scenarios and
  policies, and the limits of the correctness check;
* ``bench/metrics/<metric>.py`` — a ``read(run)`` that returns the
  metric's value from the run's record, or ``None`` where it finds
  nothing to read.

A run: refuse anything but a TPU; set up (build the surface, answer one
question on a seed outside the window's set, so that every program the
cell needs is compiled or read from the persistent cache); then ask
questions on fresh seeds until ``--seconds`` have passed (the question
that is running when they pass finishes and counts); read the peak device
memory; count each question's work where the surface has a ``tally``
for it, outside the timed questions; check a sample of the answers,
drawn from ``--seed``, against the frozen plain reference
(``bench/refsim``); print the result line.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
import traceback
from dataclasses import dataclass, field
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
CACHE_DIR = ".jax_cache"          # fixed, inside the checkout
TRACE_SECONDS = 4.0               # traced part of a --trace 1 window


class CellError(Exception):
    """The cell cannot run as ``BENCHMARK.json`` describes it."""


@dataclass
class Cell:
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list
    root: Path


@dataclass
class Run:
    """What a run recorded; the metric readers take their numbers here."""
    setup_s: float = 0.0
    setup_compiles: int = 0
    setup_compile_s: float = 0.0
    setup_cache_hits: int = 0
    window_s: float = 0.0
    window_compiles: int = 0
    questions: list = field(default_factory=list)
    failed: int = 0
    traced: int = 0               # questions asked while tracing
    trace: dict | None = None


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(root: Path, workload: str) -> Cell:
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise CellError(f"no workload {workload!r} in BENCHMARK.json; "
                        f"have {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(root / configs[w["config"]]["file"])
    traffic = load_json(root / "bench" / "traffic" / f"{w['traffic']}.json")

    def applies(m):
        return "workloads" not in m or workload in m["workloads"]

    return Cell(int(w["chips"]), config, traffic,
                [m for m in bench["end_to_end"] if applies(m)],
                [m for m in bench["per_layer"] if applies(m)], root)


def load_module(path: Path, name: str):
    """Import a file by path: metric and surface files carry names (with
    dots) that are not Python module names."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise CellError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def require_chip(chips: int):
    """The TPU devices, or exit without a result: a measurement that finds
    no accelerator never falls back to the CPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"bench: needs a TPU, JAX found {devs[0].platform!r}; "
                 f"no result")
    if len(devs) < chips:
        sys.exit(f"bench: the cell needs {chips} TPU chips, JAX found "
                 f"{len(devs)}; no result")
    return devs


def enable_cache(root: Path):
    """JAX's persistent compilation cache at one fixed path inside the
    checkout, whatever the environment says, and for every program."""
    import jax
    path = str(root / CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def span(name: str):
    """A host span in the profiler's trace (free when it is not tracing)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


def device_info(devs) -> dict:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peaks = [p for p in peaks if p is not None]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs),
            "memory_peak_bytes": max(peaks) if peaks else None}


def ask(surface, state, seed: int, i: int) -> dict:
    """One question, timed by the host clock to its last host readback."""
    from metric_math import derive_seed
    qseed = derive_seed(seed, i)
    t0 = time.perf_counter()
    with span("bench.question"):
        rec = surface.question(state, qseed)
    rec["wall_s"] = time.perf_counter() - t0
    rec["seed"] = qseed
    return rec


def measure(surface, state, seed: int, seconds: float, run: Run,
            trace_dir: str | None):
    """Questions on fresh seeds until ``seconds`` have passed.  With a
    ``trace_dir``, the profiler records the window's first questions, up
    to ``TRACE_SECONDS``, inside a ``bench.window`` span: the device's
    trace buffer holds about ten seconds of these replays' operations and
    drops what comes later, and a longer trace takes minutes to read."""
    import jax
    from repro.core.instrument import count_compiles
    traced = None
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
        traced = span("bench.window")
        traced.__enter__()

    def stop_tracing():
        traced.__exit__(None, None, None)
        jax.profiler.stop_trace()
        run.traced = len(run.questions)

    try:
        with count_compiles() as cc:
            t0 = time.perf_counter()
            i = 0
            while time.perf_counter() - t0 < seconds:
                try:
                    run.questions.append(ask(surface, state, seed, i))
                except Exception:     # a question that never answers
                    traceback.print_exc()
                    run.failed += 1
                i += 1
                if traced and time.perf_counter() - t0 >= TRACE_SECONDS:
                    stop_tracing()
                    traced = None
            run.window_s = time.perf_counter() - t0
        run.window_compiles = cc.count
    finally:
        if traced:
            stop_tracing()


def read_trace(trace_dir: str, platform: str) -> dict | None:
    import xplane_reduce
    from jax.profiler import ProfileData
    paths = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not paths:
        return None
    planes = list(ProfileData.from_file(str(paths[-1])).planes)
    print(json.dumps({"trace_bytes": paths[-1].stat().st_size, "planes": {
        p.name: [ln.name for ln in p.lines]
        for p in planes if not p.name.startswith("/host:")}}),
        file=sys.stderr)
    return xplane_reduce.reduce(planes, platform)


def read_metrics(cell: Cell, run: Run, specs: list) -> dict:
    out = {}
    for m in specs:
        reader = load_module(cell.root / "bench" / "metrics"
                             / f"{m['name']}.py", f"metric_{m['name']}")
        value = reader.read(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, devs,
             process_start: float) -> dict:
    """Set up, measure and check one cell; return the result line."""
    import surface_util
    from repro.core.instrument import count_compiles
    platform = devs[0].platform
    surface = load_module(cell.root / "bench" / "surfaces"
                          / f"{cell.traffic['surface']}.py",
                          f"surface_{cell.traffic['surface']}")
    run = Run()
    with count_compiles() as cc:
        state = surface.prepare(cell.config, cell.traffic)
        ask(surface, state, seed, -1)     # set-up's seed: not in the window
    run.setup_compiles, run.setup_compile_s = cc.count, cc.seconds
    run.setup_cache_hits = cc.cache_hits
    run.setup_s = time.perf_counter() - process_start

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        measure(surface, state, seed, seconds, run, trace_dir)
        if trace_dir:
            run.trace = read_trace(trace_dir, platform)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    # the yardstick's own count of each question's work, after the window
    tally = getattr(surface, "tally", None)
    if tally:
        for q in run.questions:
            tally(state, q)
    device = device_info(devs)
    if trace and run.trace:
        device.update(busy_s=run.trace["busy_s"],
                      window_s=run.trace["window_s"])

    # the reference runs after the peak is read, on the host's CPU, so it
    # neither sets the device's peak nor takes the chip
    t_check = time.perf_counter()
    checks = surface_util.check(surface, state, run.questions, seed)
    check_s = time.perf_counter() - t_check
    attempted = len(run.questions) + run.failed
    correct = (run.failed == 0 and attempted > 0
               and all(c["value"] <= c["limit"] for c in checks))
    print(json.dumps({"setup_s": run.setup_s,
                      "setup_programs": run.setup_compiles,
                      "setup_compile_s": run.setup_compile_s,
                      "setup_cache_hits": run.setup_cache_hits,
                      "window_s": run.window_s,
                      "window_programs_compiled": run.window_compiles,
                      "questions": len(run.questions),
                      "check_s": check_s,
                      **surface.summary(run)}), file=sys.stderr)
    result = {
        "correct": correct,
        "attempted": attempted,
        "failed": run.failed,
        "metrics": read_metrics(cell, run,
                                cell.per_layer if trace else cell.end_to_end),
        "device": device,
    }
    if trace and run.trace:
        result["breakdown"] = {"device_ops": run.trace["device_ops"],
                               "idle_gaps": run.trace["idle_gaps"]}
    result["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                        for c in checks}
    return result


def parse(argv):
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv, process_start: float):
    args = parse(argv)
    try:
        cell = load_cell(ROOT, args.workload)
    except (OSError, KeyError, CellError) as e:
        sys.exit(f"bench: {e}")
    devs = require_chip(cell.chips)
    sys.path.insert(0, str(ROOT / "src"))
    enable_cache(ROOT)
    import repro.core  # noqa: F401  (the simulator runs in float64)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), devs,
                      process_start)
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result))
    sys.stdout.flush()
    sys.stderr.flush()
    # skip interpreter teardown: the runtime's shutdown logging would
    # otherwise follow the check lines on standard error
    os._exit(0)
