"""Find where the replay on an accelerator departs from the same program on
the host's CPU.

Replays windows of a streaming benchmark cell (a ``bench/configs`` fabric
under a ``bench/traffic`` stream and its pool, plus the always-on
baseline) one policy lane at a time, through the stream's own program
(``replay._multi_segment_runner`` on a one-trace batch), once on the
default device and once on the CPU, from the same inputs.  It compares
the carry leaf by leaf after each segment; in the first segment that
departs, after each step; in the first step that departs, after each
message of the step's message phase, every message started from the
CPU's state on both devices, with the message's FSM and PerfBound
intermediates beside it; and last the step's delivery update of the
per-node clocks.  Each lane's reduced statistics (``lane_sums``) end the
report, as relative gaps per window.

A leaf departs where an integer or boolean element differs or a float's
relative gap passes ``--rtol``: the TPU holds a float64 as a pair of
float32s, so its last bits differ from the CPU's nearly everywhere, and
what matters is a decision that flips (a histogram bin, a hit or miss)
or a value that moves beyond rounding.

    PYTHONPATH=src python experiments/scripts/replay_bisect.py \\
        --config bench/configs/fattree-k26.json \\
        --traffic bench/traffic/stream-diurnal-perfbound.json \\
        --seed 501 --windows 3 --out bisect.json

On a host without an accelerator both sides run on the CPU and every
comparison reads equal (``--k`` and ``--n-nodes`` cut the fabric and the allocation for such a
run).
"""
import argparse
import json
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "src"))

import jax                                                  # noqa: E402
import jax.numpy as jnp                                     # noqa: E402

import repro.core  # noqa: E402,F401  (float64)
from repro.core import perfbound as pb                      # noqa: E402
from repro.core import replay                               # noqa: E402
from repro.core import simulator as S                       # noqa: E402
from repro.core.eee import Policy, PowerModel               # noqa: E402
from repro.streaming.drift import DriftSpec, window_trace   # noqa: E402
from repro.topology.fattree import FatTree                  # noqa: E402
from repro.topology.megafly import Megafly                  # noqa: E402
from repro.traffic.plan import compile_plan, stack_plans    # noqa: E402

MAX_MESSAGES = 8     # departing messages reported per step
ATOL = 1e-15         # an absolute gap below this is rounding (s, J)


def fabric(config: dict, k: int | None):
    spec = config["topology"]
    if spec["kind"] == "fattree":
        return FatTree(**(dict(spec["params"], k=k) if k else spec["params"]))
    return Megafly(**spec["params"])


def drift(traffic: dict, n_nodes: int, seed: int) -> DriftSpec:
    d = dict(traffic["drift"])
    params = d.pop("params")
    return DriftSpec(**d, n_nodes=n_nodes, seed=seed,
                     params=tuple(sorted(params.items())))


def host(tree):
    return jax.tree.map(np.asarray, tree)


def leaves(tree) -> dict:
    """Host leaves by path; clocks kept as bit patterns read as float64."""
    return {jax.tree_util.keystr(p): replay.host_times(v)
            for p, v in jax.tree_util.tree_leaves_with_path(tree)}


def diff(a, b, rtol: float) -> dict:
    """Leaves of two host pytrees that depart: an integer or boolean leaf
    that differs anywhere, a float leaf whose relative gap passes
    ``rtol`` anywhere (the TPU's float64 is a pair of float32s, so its
    roundings differ from the CPU's in the last bits everywhere).  For
    each: how many elements, the first, its two values and the largest
    relative gap."""
    out = {}
    la, lb = leaves(a), leaves(b)
    for k in la:
        x, y = la[k], lb[k]
        if x.dtype.kind == "f":
            with np.errstate(all="ignore"):
                rel = np.abs(x - y) / np.maximum(np.abs(y), 1e-300)
            same = (x == y) | ((x != x) & (y != y)) | (rel <= rtol) \
                | (np.abs(x - y) <= ATOL)
        else:
            same = x == y
        if same.all():
            continue
        i = np.unravel_index(np.argmin(same), x.shape) if x.ndim else ()
        d = {"n": int((~same).sum()), "of": int(x.size),
             "first": [int(j) for j in i],
             "accel": float(x[i]), "cpu": float(y[i])}
        if x.dtype.kind == "f":
            finite = rel[np.isfinite(rel)]
            d["max_rel"] = float(finite.max()) if finite.size else None
        out[k] = d
    return out


def rounding(a, b) -> float:
    """The largest relative gap of any float leaf: rounding, where
    ``diff`` finds no departure."""
    worst = 0.0
    la, lb = leaves(a), leaves(b)
    for k in la:
        x, y = la[k], lb[k]
        if x.dtype.kind != "f":
            continue
        with np.errstate(all="ignore"):
            rel = np.abs(x - y) / np.maximum(np.abs(y), 1e-300)
        rel = rel[np.isfinite(rel) & ~((x == y) | (x != x))
                  & (np.abs(x - y) > ATOL)]
        if rel.size:
            worst = max(worst, float(rel.max()))
    return worst


def lane(batch, pol: Policy, pm: PowerModel, dev):
    """The stream's program for one lane, placed on ``dev``."""
    proto, params, carry = replay.init_lanes_multi([pol], batch)
    put = lambda t: jax.device_put(t, dev)              # noqa: E731
    return proto, put(params), put(carry), put(batch.part_mask)


def run_seg(proto, pm, batch, seg, params, carry, part, xs):
    md, ns = replay._seg_flags(seg, proto)
    run = replay._multi_segment_runner(proto, pm, batch.n_links, seg.cap,
                                       md, ns)
    carry = jax.tree.map(jnp.copy, carry)     # the runner donates it
    out, _ = run(carry[0], params, carry[1], carry[2], carry[3], part, xs)
    return out


def probe(net, m, proto: Policy, pm: PowerModel, n_links: int, p):
    """One message's FSM decisions and PerfBound intermediates: what its
    hops record, the selection's feasible bins and the new timers."""
    links, dirs, nhops, t_inj, nbytes, valid = m
    active, lp, dp = S._slot_rows(links, dirs, nhops, valid, n_links)
    g = {"free": net["dir_free"][dp], "last": net["last_end"][lp],
         "dl": net["deadline"][lp], "dl2": net["deadline2"][lp]}
    ns = S._slot_compute(g, m, active, proto, pm, p)
    C = S.clock_of(proto)
    t_end = C.val(ns["t_end"])
    out = {k: ns[k] for k in ("gap", "t_avail", "t_start", "t_end",
                              "asleep", "deep", "wake_add", "sleep_add",
                              "sleep2_add", "delivery")}
    out["active"] = active
    pred = net["pred"]
    if proto.adaptive or proto.record_hist:
        out["recorded"] = active & (ns["gap"] > 0)
        gb = ns["gap_bits"]
        out["bin"] = pb.bin_index(ns["gap"] if gb is None else gb, proto, p)
        pred = pb.record_gaps(pred, lp, ns["gap"], ns["t_now"], active,
                              proto, p, gap_bits=gb)
        pred = pb.record_hops(pred, lp, nhops - jnp.arange(links.shape[-1]),
                              active, proto)
    if proto.kind == "perfbound_correct":
        ratio = ns["gap"] / jnp.maximum(net["pred"]["tpdt"][lp], 1e-12)
        pred = pb.record_outcomes(pred, lp, ns["asleep"], ratio, active,
                                  proto)
        out["cf"] = pb.pbc_cf(pred["reg"][lp], pred["ratio_log"][lp],
                              pred["n_seen"][lp], proto)
    if proto.adaptive:
        ccum = pb._suffix_sum(pred["counts"][lp])
        X = jnp.maximum(t_end - pred["win_start"][lp], 0.0)
        out["l"] = pb.l_factor(pred["hops"][lp], p["bound"])
        out["N"] = out["l"] * X / p["t_w"]
        feasible = ccum <= out["N"][..., None]
        out["bin_pdt"] = jnp.where(feasible.any(-1),
                                   jnp.argmax(feasible, axis=-1), -1)
        if proto.kind == "perfbound_dual":
            t, td = pb.compute_tpdt_tdst(pred, lp, t_end, p["t_w"], proto,
                                         p)
            scum = pb._suffix_sum(pred["sums"][lp])
            resid = scum / jnp.maximum(ccum, 1e-30) \
                - pb.bin_centers(proto, p)
            ok = (ccum > 0) & (resid >= pb.deep_breakeven(p))
            out["bin_dst"] = jnp.where(ok.any(-1), jnp.argmax(ok, axis=-1),
                                       -1)
            out["t_dst"] = td
        else:
            t = pb.compute_tpdt(pred, lp, t_end, p["t_w"], proto, p)
        out["tpdt"] = t
    return out


def messages(proto, pm, batch, seg, step, params, carry, accel, cpu,
             rtol):
    """Replay one step message by message from the CPU's state on both
    devices; report the messages whose outputs depart, then the step's
    delivery update of ``ready``."""
    x = {k: np.asarray(v)[0, step] for k, v in seg.xs.items()}
    net = jax.tree.map(lambda v: v[0, 0], host(carry[0]))
    ready = replay.host_times(host(carry[1])[0, 0]) + seg.host_delta[0, step]
    exact = S.clock_of(proto) is not S.ieee.FloatClock
    p = {k: v[0] for k, v in host(params).items()}
    t_inj = ready[x["src"]]
    order = np.argsort(np.where(x["valid"], t_inj, np.inf), kind="stable")
    nv = int(x["valid"].sum())
    step_fn = jax.jit(lambda n, m, q: S._message_step(n, m, proto, pm,
                                                      batch.n_links, q))
    probe_fn = jax.jit(lambda n, m, q: probe(n, m, proto, pm,
                                             batch.n_links, q))
    out, deliveries = [], []
    for k in range(nv):
        o = order[k]
        t = S.ieee.host_bits(t_inj[o]) if exact else t_inj[o]
        m = (x["links"][o], x["dirs"][o], x["nhops"][o], t,
             x["nbytes"][o], x["valid"][o])
        res = {d: host(step_fn(*jax.device_put((net, m, p), dev)))
               for d, dev in (("accel", accel), ("cpu", cpu))}
        gaps = diff(res["accel"], res["cpu"], rtol)
        if gaps and len(out) < MAX_MESSAGES:
            pr = {d: host(probe_fn(*jax.device_put((net, m, p), dev)))
                  for d, dev in (("accel", accel), ("cpu", cpu))}
            out.append({"message": k, "slot": int(o),
                        "departs": gaps,
                        "intermediates": diff(pr["accel"], pr["cpu"], rtol),
                        "cpu_values": {n: np.asarray(v).tolist()
                                       for n, v in pr["cpu"].items()
                                       if np.asarray(v).size <= 8}})
        net, (d, _, _) = res["cpu"]
        deliveries.append(float(replay.host_times(d)))
    dst = x["dst"][order[:nv]]
    want = ready.copy()
    np.maximum.at(want, dst, np.asarray(deliveries))
    fn = jax.jit(lambda r, i, v: r.at[i].max(v))
    got = host(fn(*jax.device_put((ready, dst, np.asarray(deliveries)),
                                  accel)))
    bad = np.flatnonzero(np.abs(got - want) > rtol * np.abs(want))
    dup = int(len(dst) - len(np.unique(dst)))
    return {"messages": out, "live": nv, "repeated_destinations": dup,
            "ready_scatter_max_departs": [
                {"node": int(i), "accel": float(got[i]),
                 "cpu": float(want[i])} for i in bad[:MAX_MESSAGES]]}


def bisect_window(batch, pol, pm, accel, cpu, rtol) -> dict:
    proto, pa, ca, part_a = lane(batch, pol, pm, accel)
    _, pc, cc, part_c = lane(batch, pol, pm, cpu)
    rep = {"kind": pol.kind, "segments": []}
    for si, seg in enumerate(batch.segments):
        # the host's deltas: a float64 read back from the TPU is rounded
        xs = dict(replay.segment_xs(seg, proto), delta=seg.host_delta)
        xa, xc = jax.device_put(xs, accel), jax.device_put(xs, cpu)
        na = run_seg(proto, pm, batch, seg, pa, ca, part_a, xa)
        nc = run_seg(proto, pm, batch, seg, pc, cc, part_c, xc)
        gaps = diff(host(na), host(nc), rtol)
        srep = {"segment": si, "cap": seg.cap, "steps": seg.n_steps,
                "departs": gaps, "rounding": rounding(host(na), host(nc))}
        rep["segments"].append(srep)
        if gaps and "first_step" not in rep:
            # from the CPU's carry on both sides, one step at a time
            state = cc
            for i in range(seg.n_steps):
                one = {k: v[:, i:i + 1] for k, v in xs.items()}
                sa = run_seg(proto, pm, batch, seg, pa,
                             jax.device_put(host(state), accel), part_a,
                             jax.device_put(one, accel))
                sc = run_seg(proto, pm, batch, seg, pc, state, part_c,
                             jax.device_put(one, cpu))
                g = diff(host(sa), host(sc), rtol)
                if g:
                    rep["first_step"] = {
                        "segment": si, "step": i, "departs": g,
                        **messages(proto, pm, batch, seg, i, pc, state,
                                   accel, cpu, rtol)}
                    break
                state = sc
        ca, cc = na, nc
    t_end = {}
    sums = {}
    for d, c, part in (("accel", ca, part_a), ("cpu", cc, part_c)):
        te = replay.host_times(replay._participant_max_multi(part, c[1]))
        t_end[d] = te
        sums[d] = S.lane_sums(c[0], te, [pol], batch.n_links)
    with np.errstate(all="ignore"):
        rel = np.abs(sums["accel"] - sums["cpu"]) \
            / np.maximum(np.abs(sums["cpu"]), 1e-300)
    rep["lane_sums_rel"] = dict(zip(S.LANE_SUMS, rel[0, 0].tolist()))
    rep["t_end"] = {d: float(v[0, 0]) for d, v in t_end.items()}
    return rep


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, default=501)
    ap.add_argument("--windows", type=int, default=2,
                    help="windows 0 .. n-1 of the stream")
    ap.add_argument("--kinds", default="",
                    help="comma-separated policy kinds (default: all)")
    ap.add_argument("--k", type=int, default=None,
                    help="cut a fat-tree to arity k")
    ap.add_argument("--rtol", type=float, default=1e-9,
                    help="relative gap of a float leaf that departs")
    ap.add_argument("--n-nodes", type=int, default=None,
                    help="allocation size (default: the configuration's)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    config = json.loads(Path(args.config).read_text())
    traffic = json.loads(Path(args.traffic).read_text())
    topo = fabric(config, args.k)
    pm = PowerModel(**config["power_model"])
    spec = drift(traffic, args.n_nodes or config["n_nodes"], args.seed)
    pols = {"baseline": Policy(kind="none"),
            **{n: Policy(**kw) for n, kw in traffic["pool"].items()}}
    kinds = set(filter(None, args.kinds.split(",")))
    accel, cpu = jax.devices()[0], jax.devices("cpu")[0]
    report = {"accel": accel.device_kind, "seed": args.seed, "lanes": {}}
    for w in range(args.windows):
        batch = stack_plans([compile_plan(window_trace(spec, topo, w),
                                          topo)])
        for name, pol in pols.items():
            if kinds and pol.kind not in kinds:
                continue
            rep = bisect_window(batch, pol, pm, accel, cpu, args.rtol)
            report["lanes"].setdefault(name, []).append(
                dict(rep, window=w))
            first = rep.get("first_step", {})
            print(json.dumps({
                "window": w, "lane": name, "kind": pol.kind,
                "first_step": [first.get("segment"), first.get("step")],
                "step_departs": sorted(first.get("departs", {})),
                "messages_departing": [m["message"] for m in
                                       first.get("messages", [])],
                "ready_max_departs": len(
                    first.get("ready_scatter_max_departs", [])),
                "repeated_destinations": first.get(
                    "repeated_destinations"),
                "rounding": max(sg["rounding"] for sg in rep["segments"]),
                "lane_sums_rel": max(rep["lane_sums_rel"].values())}),
                flush=True)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(report, indent=1))


if __name__ == "__main__":
    main()
