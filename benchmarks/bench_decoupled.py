"""Decoupled (Pallas-kernel) fast path vs coupled simulator: accuracy of
the first-order approximation and its speedup — the quantified trade of
DESIGN.md §3 (TPU-native rethink).  The kernels compile for the TPU; off
the TPU this module fails rather than interpret them."""
from __future__ import annotations

import numpy as np

from benchmarks.common import PM, Row, get_apps, get_topo, timed
from repro.core import decoupled as D
from repro.core import simulator as S
from repro.core.eee import Policy


def run(scale: str = "small"):
    topo = get_topo(scale)
    rows = []
    trace = get_apps(scale, topo)["alexnet"]
    (res0, events), us_base = timed(
        S.simulate_trace, trace, topo, Policy(kind="none"), PM, True)
    (streams), us_stream = timed(D.events_to_streams, events, topo.n_links,
                                 res0.makespan)
    gaps, durs, tail = streams

    for t_pdt in (1e-5, 1e-3):
        pol = Policy(kind="fixed", t_pdt=t_pdt, sleep_state="deep_sleep")
        coupled, us_c = timed(S.simulate_trace, trace, topo, pol, PM)
        coupled = coupled[0]
        dec, us_d = timed(D.evaluate_fixed, gaps, durs, tail, t_pdt, pol, PM)
        err = abs(dec["link_energy"] - coupled.link_energy) \
            / coupled.link_energy
        rows.append(Row(
            f"decoupled/alexnet/t={t_pdt:g}", us_d,
            f"energy_err={100*err:.2f}% "
            f"wake_err={abs(float(np.asarray(dec['n_wake']).sum()) - coupled.n_wake_transitions):.0f} "
            f"speedup_x={us_c/max(us_d,1):.1f} coupled_us={us_c:.0f}"))
    rows.append(Row("decoupled/stream_build", us_stream,
                    f"events={sum(len(e[0]) for e in events)}"))
    return rows
