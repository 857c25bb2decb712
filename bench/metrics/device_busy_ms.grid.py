"""Device milliseconds per grid question: the union of the intervals in
which an operation ran on the device over the traced part of the window,
averaged over the devices, divided by the questions asked in it."""


def read(run):
    n = sum(1 for q in run.questions[:run.traced] if "hops" in q)
    if not n or run.trace is None:
        return None
    return 1e3 * run.trace["busy_s"] / n
