"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's
device numbers.

* device busy: the union of the intervals in which an operation ran on
  the device, inside the traced window, averaged over the devices;
* the window: the host span named ``bench.window`` that the harness puts
  around the traced part of its measured loop;
* a breakdown: the device operations that took most time, and the
  longest idle gaps, each labelled by the innermost ``bench.*`` host span
  that covers its midpoint (what the host was doing meanwhile).

On a TPU the operations are the events of each ``/device:TPU:<n>``
plane's ``XLA Modules`` line: one event per execution of a compiled
program.  (The ``XLA Ops`` line holds every operation inside the
programs' loops, millions of events a second, too many to read within a
run's time.)  The CPU backend has no device plane: there its XLA client
threads run the operations, which is what the checked-in test trace
records.
"""
from __future__ import annotations

from collections import defaultdict

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."


def _events(line):
    for e in line.events:
        yield (short_name(e.name), float(e.start_ns),
               float(e.start_ns + e.duration_ns))


def short_name(name: str) -> str:
    """An HLO event's name without its signature: ``%while.8 = (...)``
    becomes ``while.8``."""
    return name.split(" = ", 1)[0].lstrip("%")


def device_op_lines(planes, platform: str) -> dict:
    """``{device plane name: [(name, start_ns, end_ns), ...]}``."""
    out = {}
    for plane in planes:
        if platform == "tpu":
            if not plane.name.startswith("/device:TPU:"):
                continue
            lines = [ln for ln in plane.lines if ln.name == "XLA Modules"]
            out[plane.name] = [ev for ln in lines for ev in _events(ln)]
        elif platform == "cpu" and plane.name == "/host:CPU":
            out[plane.name] = [
                ev for ln in plane.lines
                if ln.name.startswith("tf_XLAPjRtCpuClient")
                for ev in _events(ln)
                if ev[2] > ev[1] and not ev[0].startswith(
                    ("ThreadpoolListener", "end: "))]
    return out


def host_spans(planes) -> list:
    """Every ``bench.*`` host span: ``[(name, start_ns, end_ns), ...]``."""
    return [ev for plane in planes if plane.name.startswith("/host:")
            for ln in plane.lines for ev in _events(ln)
            if ev[0].startswith(SPAN_PREFIX)]


def union(intervals) -> list:
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _label(t, spans) -> str:
    """The innermost host span covering time ``t``."""
    best = None
    for name, s, e in spans:
        if s <= t <= e and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else "outside bench spans"


def reduce(planes, platform: str, top: int = 10) -> dict | None:
    """Busy seconds (mean over devices), window seconds and breakdown.
    ``None`` where the trace holds no window span or no device operation."""
    planes = list(planes)
    spans = host_spans(planes)
    windows = [(s, e) for name, s, e in spans if name == WINDOW_SPAN]
    devices = {k: v for k, v in device_op_lines(planes, platform).items()
               if v}
    if not windows or not devices:
        return None
    lo, hi = min(s for s, _ in windows), max(e for _, e in windows)
    busy_ns = 0.0
    op_ns = defaultdict(float)
    gaps = []
    for ops in devices.values():
        merged = union(_clip([(s, e) for _, s, e in ops], lo, hi))
        busy_ns += sum(e - s for s, e in merged)
        for name, s, e in ops:
            s, e = max(s, lo), min(e, hi)
            if e > s:
                op_ns[name] += e - s
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    n_dev = len(devices)
    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "busy_s": busy_ns / n_dev * 1e-9,
        "window_s": (hi - lo) * 1e-9,
        "devices": n_dev,
        "device_ops": [[name, ns / n_dev * 1e-9] for name, ns in sorted(
            op_ns.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[_label((s + e) / 2, spans), (e - s) * 1e-9]
                      for s, e in gaps[:top]],
    }


def reduce_file(path, platform: str, top: int = 10) -> dict | None:
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(str(path)).planes, platform, top)
