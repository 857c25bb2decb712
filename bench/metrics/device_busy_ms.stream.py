"""Device milliseconds per re-advised advisor window: the union of the
intervals in which an operation ran on the device over the traced part of
the window, averaged over the devices, divided by the advisor windows
re-advised in it."""


def read(run):
    n = sum(len(q.get("readvise_s", ())) for q in run.questions[:run.traced])
    if not n or run.trace is None:
        return None
    return 1e3 * run.trace["busy_s"] / n
