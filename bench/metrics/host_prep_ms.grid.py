"""Mean host milliseconds per grid question spent synthesizing its traces
and lowering their plans (``scenarios.spec.build_trace`` and
``traffic.plan.compile_plan``), by the harness's host clock around them."""


def read(run):
    qs = [q["prep_s"] for q in run.questions if "hops" in q]
    return 1e3 * sum(qs) / len(qs) if qs else None
