"""The program's own record of each re-advised window of a streaming
question: ``advise_stream`` reports per timeline row the host milliseconds
of each layer (``host_ms``, summed ``repro.*`` spans) and the window's
counters (``counts``).  The per-layer stream metrics average them per
window, over the windows of the questions asked after the traced part of
the run (the profiler off); where there are none, over every question.
A program whose report has no ``host_ms`` gives nothing to read."""


def windows(run) -> list:
    """``[(readvise_s, timeline row), ...]`` of the windows the stream
    metrics average over; empty where the report has no ``host_ms``."""
    qs = [q for q in run.questions[run.traced:] if "readvise_s" in q] \
        or [q for q in run.questions if "readvise_s" in q]
    out = [(s, row) for q in qs
           for s, row in zip(q["readvise_s"], q["report"]["timeline"])]
    return out if out and all("host_ms" in r for _, r in out) else []


def _mean(values: list):
    return sum(values) / len(values) if values else None


def host_ms(run, layer: str):
    """Mean host milliseconds a window spent in ``layer``."""
    return _mean([row["host_ms"][layer] for _, row in windows(run)])


def count(run, name: str):
    """Mean of the counter ``name`` per window."""
    return _mean([row["counts"][name] for _, row in windows(run)])


def unspanned_ms(run):
    """Mean milliseconds of a window's re-advice, by the harness's clock,
    that none of the report's layers covers."""
    return _mean([1e3 * s - sum(row["host_ms"].values())
                  for s, row in windows(run)])
