"""Share of the traced part of a grid cell's window in which no
operation ran on the device."""


def read(run):
    if run.trace is None or not any("hops" in q for q in run.questions):
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])
