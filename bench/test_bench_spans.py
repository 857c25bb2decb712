"""The stream cell's per-layer metrics read from the program's own
per-window record, on the CPU at the harness tests' small size."""
import copy
import time

import jax
import pytest

import harness
from test_bench_harness import STREAM, small_checkout

SPANNED = ("synth_ms.stream", "lower_ms.stream", "dispatch_ms.stream",
           "device_wait_ms.stream", "readback_ms.stream",
           "summarize_ms.stream", "decide_ms.stream")
NEW = SPANNED + ("placed_bytes.stream", "replay_device_ms.stream",
                 "readback_bytes.stream", "unspanned_ms.stream")


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """A traced run of the small stream cell and the harness's record."""
    root = small_checkout(tmp_path_factory.mktemp("checkout"))
    runs = []

    class Run(harness.Run):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            runs.append(self)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(harness, "Run", Run)
        out = harness.run_cell(harness.load_cell(root, STREAM),
                               2 ** 31 + 5, 0.2, True, jax.devices(),
                               time.perf_counter())
    return root, out, runs[0]


def _windows(run):
    qs = run.questions[run.traced:] or run.questions
    return [(1e3 * s, row) for q in qs
            for s, row in zip(q["readvise_s"], q["report"]["timeline"])]


def test_every_new_metric_is_reported(traced):
    """Each new metric is read and positive.  The CPU backend's trace names
    no compiled program (its device operations are the HLO instructions
    the client threads ran), so there the replay programs' device time is
    read from a trace that names them, as a TPU's ``XLA Modules`` do."""
    root, out, run = traced
    assert out["correct"] is True
    for name in NEW:
        if name != "replay_device_ms.stream":
            assert out["metrics"][name]["value"] > 0, name
    assert "replay_device_ms.stream" not in out["metrics"]
    named = copy.copy(run)
    named.trace = dict(run.trace, device_ops=[
        ["jit_replay_prefix_8421_", 0.003], ["jit_replay_scan_77_", 0.001],
        ["jit_add_5_", 0.5]])
    cell = harness.load_cell(root, STREAM)
    spec = [m for m in cell.per_layer
            if m["name"] == "replay_device_ms.stream"]
    windows = sum(len(q["readvise_s"]) for q in run.questions[:run.traced])
    assert windows > 0
    got = harness.read_metrics(cell, named, spec)
    assert got["replay_device_ms.stream"]["value"] == \
        pytest.approx(4.0 / windows, rel=1e-12)


def test_layers_add_up_to_the_mean_readvice(traced):
    """The seven layer means plus what no span covers are the mean
    re-advice over the windows the metrics read."""
    _, out, run = traced
    ms = [s for s, _ in _windows(run)]
    mean = sum(ms) / len(ms)
    total = sum(out["metrics"][n]["value"]
                for n in SPANNED + ("unspanned_ms.stream",))
    assert total == pytest.approx(mean, rel=1e-9)
    assert out["metrics"]["unspanned_ms.stream"]["value"] < mean


def test_metrics_read_the_windows_after_the_traced_part(traced):
    """Questions asked after the profiler stopped are the ones averaged:
    a run whose traced part is the cell's run, followed by a copy of it
    with every host layer and counter doubled, reads the copy."""
    root, out, run = traced
    later = copy.deepcopy(run.questions)
    for q in later:
        for row in q["report"]["timeline"]:
            row["host_ms"] = {k: 2 * v for k, v in row["host_ms"].items()}
            row["counts"] = {k: 2 * v for k, v in row["counts"].items()}
    mixed = copy.copy(run)
    mixed.questions = run.questions + later
    mixed.traced = len(run.questions)
    cell = harness.load_cell(root, STREAM)
    specs = [m for m in cell.per_layer if m["name"] in NEW]
    got = harness.read_metrics(cell, mixed, specs)
    for name in SPANNED + ("placed_bytes.stream", "readback_bytes.stream"):
        assert got[name]["value"] == pytest.approx(
            2 * out["metrics"][name]["value"], rel=1e-12), name
    assert "replay_device_ms.stream" not in got


def test_readers_find_nothing_in_a_report_without_layers(traced):
    """A program whose report carries no ``host_ms`` (and whose replay
    programs are not named by executor) gives these readers nothing to
    read, and none of them raises."""
    root, _, run = traced
    bare = copy.deepcopy(run)
    for q in bare.questions:
        for row in q["report"]["timeline"]:
            del row["host_ms"], row["counts"]
    bare.trace["device_ops"] = [
        [name.replace("jit_replay_", "jit_run_"), s]
        for name, s in bare.trace["device_ops"]]
    cell = harness.load_cell(root, STREAM)
    specs = [m for m in cell.per_layer if m["name"] in NEW]
    assert len(specs) == len(NEW)
    assert harness.read_metrics(cell, bare, specs) == {}
