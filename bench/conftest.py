"""Set-up shared by the harness tests.

A traced run of the stream cell reports, beside the harness's own
layers, the per-layer metrics read from the program's per-window record
(``window_layers.py``), so the exact set of layers that
``test_bench_harness.test_traced_run_reports_the_layers`` expects of that
cell names them too.  ``replay_device_ms.stream`` is not among them on
the CPU: its trace names no compiled program."""
import test_bench_harness
from test_bench_spans import NEW

test_bench_harness.LAYERS[test_bench_harness.STREAM] |= (
    set(NEW) - {"replay_device_ms.stream"})
