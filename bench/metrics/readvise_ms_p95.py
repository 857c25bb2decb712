"""95th percentile (nearest rank) of the wall milliseconds of one advisor
window's re-advice, over every window re-advised by the questions the
window completed.  The sample count is on the run's standard error."""
from metric_math import percentile


def read(run):
    ms = [1e3 * s for q in run.questions for s in q.get("readvise_s", ())]
    return percentile(ms, 95)[0]
