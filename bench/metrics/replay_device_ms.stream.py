"""Device milliseconds per re-advised window in the replay programs: the
summed device time of the operations named ``jit_replay_*`` (one per
segment executor, all among the trace's ten longest operations) over the
traced part of the window, divided by the advisor windows re-advised in
it."""


def read(run):
    n = sum(len(q.get("readvise_s", ())) for q in run.questions[:run.traced])
    if not n or run.trace is None:
        return None
    s = sum(sec for name, sec in run.trace["device_ops"]
            if name.startswith("jit_replay_"))
    return 1e3 * s / n if s else None
