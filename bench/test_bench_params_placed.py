"""The stream cell's ``params_placed.stream``, read from the program's
per-window counter, on the CPU at the harness tests' small size.

The stream cell reports this metric beside the layers that
``conftest.py`` names, so this module adds it to the exact set of layers
that ``test_bench_harness.test_traced_run_reports_the_layers`` expects of
that cell.  That takes effect once this module is collected, as it is in
a run of the whole of ``bench/``."""
import copy

import harness
import test_bench_harness
from test_bench_harness import STREAM
from test_bench_spans import _windows, traced  # noqa: F401 (a fixture)

NAME = "params_placed.stream"

test_bench_harness.LAYERS[STREAM] |= {NAME}


def test_params_placed_reads_zero_once_set_up(traced):
    """The set-up question places every group's parameters, so no window
    of the measured questions places any."""
    _, out, run = traced
    assert out["metrics"][NAME]["value"] == 0
    assert all(row["counts"]["params_placed"] == 0
               for _, row in _windows(run))


def test_params_placed_reads_nothing_without_its_counter(traced):
    """A program whose windows count no ``params_placed`` (the other
    counters kept) gives that reader nothing, and the others still read."""
    root, out, run = traced
    bare = copy.deepcopy(run)
    for q in bare.questions:
        for row in q["report"]["timeline"]:
            del row["counts"]["params_placed"]
    cell = harness.load_cell(root, STREAM)
    specs = [m for m in cell.per_layer
             if m["name"] in (NAME, "placed_bytes.stream")]
    assert len(specs) == 2
    got = harness.read_metrics(cell, bare, specs)
    assert set(got) == {"placed_bytes.stream"}
    assert got["placed_bytes.stream"] == out["metrics"]["placed_bytes.stream"]
