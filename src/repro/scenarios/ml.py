"""ML-training scenario synthesis from published model shapes.

Lowers a :class:`~repro.scenarios.models.ModelConfig` plus a 3D parallelism
grid (DP x PP x TP) into the phase-structured trace language: per-stage
forward/backward compute, fused tensor-parallel activation all-reduces,
pipeline point-to-point activation/gradient transfers, and bucketed
data-parallel gradient all-reduces — the collective schedule a training
step of that architecture actually puts on the network.

Approximations (traffic structure, not training math):

* per-layer TP all-reduces fuse into two per stage pass (attention-side and
  MLP-side aggregates) with the stage's total volume preserved — keeps
  trace length bounded by the grid, not by ``num_layers``;
* compute phases derive from the analytic per-stage parameter count
  (``ModelConfig.layer_param_count``) at a nominal accelerator throughput
  — their role is realistic gap structure between network phases (what the
  power policies react to), not runtime prediction;
* the DP gradient all-reduce runs after the backward pipeline drains
  (no overlap), split into ``grad_buckets`` buckets per stage.

Node layout on the allocation: ``index(d, s, t) = (d*pp + s)*tp + t`` —
TP groups are contiguous (they carry the densest traffic), pipeline
neighbors sit ``tp`` apart, DP replicas ``pp*tp`` apart.
"""
from __future__ import annotations

import numpy as np

from repro.scenarios.models import get_model
from repro.scenarios.spec import builder, rng
from repro.traffic import collectives as C
from repro.traffic.generators import allocate
from repro.traffic.trace import Trace


def derive_grid(n_nodes: int, dp: int = 0, tp: int = 0, pp: int = 0):
    """Fill in unset (0) grid dims for ``n_nodes`` participants.

    Defaults: TP 2 from 8 nodes up, PP 2 from 16 nodes up, DP takes the
    rest.  All dims must be powers of two (collective algorithms) and
    multiply to ``n_nodes``.
    """
    assert n_nodes >= 1 and (n_nodes & (n_nodes - 1)) == 0, \
        f"ml_training needs a power-of-two allocation, got {n_nodes}"
    tp = tp or (2 if n_nodes >= 8 else 1)
    pp = pp or (2 if n_nodes >= 16 else 1)
    dp = dp or n_nodes // (tp * pp)
    assert dp * tp * pp == n_nodes, \
        f"dp*tp*pp = {dp}*{tp}*{pp} != n_nodes = {n_nodes}"
    for d in (dp, tp, pp):
        assert d >= 1 and (d & (d - 1)) == 0, f"non-power-of-two dim {d}"
    return dp, tp, pp


def _merged(rounds_per_group):
    """Merge per-group collective rounds into shared message steps: round r
    of every group lands in ONE step (the groups run concurrently)."""
    return [np.concatenate(rs) for rs in zip(*rounds_per_group)]


@builder("ml_training")
def ml_training(topo, n_nodes, seed, arch, iters=2, dp=0, tp=0, pp=0,
                tokens_per_iter=8192, micro_batches=2, grad_bytes=2,
                act_bytes=2, hw_flops=100e12, opt_bw=200e9, grad_buckets=4,
                mapping="linear"):
    """One trace = ``iters`` training steps of ``arch`` on a DP x PP x TP
    grid (unset dims derived from ``n_nodes``, see ``derive_grid``)."""
    cfg = get_model(arch)
    dp, tp, pp = derive_grid(n_nodes, dp, tp, pp)
    nodes = allocate(topo, n_nodes, mapping, seed)
    t = Trace(nodes=nodes, name=f"ml-{arch}")
    r = rng(seed)

    def idx(d, s, tq):
        return (d * pp + s) * tp + tq

    stages = [np.asarray([idx(d, s, tq) for d in range(dp)
                          for tq in range(tp)]) for s in range(pp)]
    L = cfg.num_layers
    lps = -(-L // pp)                            # layers per stage (ceil)
    stage_layers = [min(L - s * lps, lps) for s in range(pp)]
    layer_b = cfg.layer_param_count() * grad_bytes
    stage_param_b = [n * layer_b for n in stage_layers]
    stage_param_b[0] += cfg.embed_param_count() * grad_bytes

    tokens_micro = max(tokens_per_iter // (dp * micro_batches), 1)
    act_volume = tokens_micro * cfg.d_model * act_bytes   # one stream copy
    fwd_secs = [2 * (stage_param_b[s] // grad_bytes) * tokens_micro
                / (tp * hw_flops) for s in range(pp)]

    def stage_compute(s, secs):
        arr = np.zeros(n_nodes, np.float64)
        arr[stages[s]] = secs
        t.compute(arr)

    def tp_allreduce(s, nbytes):
        if tp < 2 or nbytes <= 0:
            return
        groups = [nodes[[idx(d, s, tq) for tq in range(tp)]]
                  for d in range(dp)]
        t.rounds(_merged([C.allreduce(g, max(int(nbytes), 64))
                          for g in groups]))

    def p2p(s_from, s_to, nbytes):
        msgs = [[int(nodes[idx(d, s_from, tq)]), int(nodes[idx(d, s_to, tq)]),
                 max(int(nbytes), 64)]
                for d in range(dp) for tq in range(tp)]
        t.messages(msgs)

    # -- setup: weight shards to every rank, jittered init work ------------
    t.rounds(C.broadcast(nodes, max(stage_param_b[0] // tp, 64)))
    t.compute(r.uniform(5e-3, 15e-3, n_nodes))

    for _ in range(iters):
        for _m in range(micro_batches):
            for s in range(pp):                  # forward pipeline
                stage_compute(s, fwd_secs[s])
                tp_allreduce(s, stage_layers[s] * act_volume)   # attn side
                tp_allreduce(s, stage_layers[s] * act_volume)   # mlp side
                if s < pp - 1:
                    p2p(s, s + 1, act_volume // tp)
            for s in reversed(range(pp)):        # backward pipeline
                stage_compute(s, 2 * fwd_secs[s])
                tp_allreduce(s, 2 * stage_layers[s] * act_volume)
                if s > 0:
                    p2p(s, s - 1, act_volume // tp)
        if dp > 1:                               # bucketed gradient sync
            groups, sizes = [], []
            for s in range(pp):
                for tq in range(tp):
                    groups.append(nodes[[idx(d, s, tq) for d in range(dp)]])
                    sizes.append(max(stage_param_b[s]
                                     // (tp * grad_buckets), 64))
            merged = _merged([C.allreduce(g, b)
                              for g, b in zip(groups, sizes)])
            for _k in range(grad_buckets):
                t.rounds(merged)
        for s in range(pp):                      # optimizer update
            stage_compute(s, stage_param_b[s] / (tp * opt_bw))
    t.rounds(C.allreduce(nodes, 64), barrier_last=True)   # loss scalar
    return t


@builder("moe_training")
def moe_training(topo, n_nodes, seed, arch, iters=2, layer_groups=4,
                 tokens_per_iter=8192, act_bytes=2, grad_bytes=2,
                 hw_flops=100e12, opt_bw=200e9, capacity_factor=1.25,
                 mapping="linear"):
    """Expert-parallel MoE training steps: token-routing all-to-alls.

    One trace = ``iters`` training steps of a MoE ``arch`` (e.g.
    ``qwen3-moe-30b-a3b``) sharded expert-parallel over the whole
    allocation.  Each of ``layer_groups`` fused layer blocks runs
    attention/router compute, a **dispatch all-to-all** (top-k routed token
    activations), expert FFN compute, and a **combine all-to-all** — then
    the backward mirror (gradients retrace the routes at 2x compute) and an
    expert-gradient all-reduce per step.  The all-to-all phases produce the
    dense symmetric bursts separated by compute gaps that distinguish MoE
    traffic from the dense-model pipeline of ``ml_training``.
    """
    cfg = get_model(arch)
    assert cfg.num_experts > 0, f"{arch} is not a MoE config"
    nodes = allocate(topo, n_nodes, mapping, seed)
    t = Trace(nodes=nodes, name=f"moe-{arch}")
    r = rng(seed)

    L = cfg.num_layers
    groups = min(layer_groups, L)
    layers_per = -(-L // groups)
    # routed token volume per device per layer: every token ships to its
    # top-k experts (capacity-padded), spread over the EP group
    topk = max(cfg.experts_per_token, 1)
    tok_dev = max(tokens_per_iter // n_nodes, 1)
    route_bytes = int(tok_dev * topk * capacity_factor * cfg.d_model
                      * act_bytes)
    # per-device expert shard: every layer's full expert grid split over
    # the allocation (the gradient sync/optimizer phases scale with the
    # whole stack, like ml_training's per-stage stage_param_b)
    expert_param_b = cfg.layer_param_count() * L * grad_bytes
    shard_param_b = max(expert_param_b // n_nodes, 64)
    attn_secs = 2 * (cfg.d_model * cfg.d_model * 4) * tok_dev / hw_flops
    ffn_secs = (2 * 3 * cfg.d_model * cfg.d_ff * topk
                * capacity_factor * tok_dev) / hw_flops

    def a2a(nbytes):
        t.rounds(C.alltoall(nodes, max(int(nbytes), 64)))

    # weight-shard broadcast + jittered init
    t.rounds(C.broadcast(nodes, shard_param_b))
    t.compute(r.uniform(5e-3, 15e-3, n_nodes))

    for _ in range(iters):
        for _g in range(groups):                 # forward blocks
            t.compute(r.uniform(0.9, 1.1, n_nodes) * attn_secs * layers_per)
            a2a(route_bytes * layers_per)        # dispatch
            t.compute(r.uniform(0.9, 1.1, n_nodes) * ffn_secs * layers_per)
            a2a(route_bytes * layers_per)        # combine
        for _g in range(groups):                 # backward blocks (2x)
            t.compute(2 * r.uniform(0.9, 1.1, n_nodes) * ffn_secs
                      * layers_per)
            a2a(2 * route_bytes * layers_per)    # grad dispatch + combine
        # expert/attention gradient sync + optimizer
        t.rounds(C.allreduce(nodes, shard_param_b))
        t.compute(np.full(n_nodes, shard_param_b / opt_bw))
    t.rounds(C.allreduce(nodes, 64), barrier_last=True)   # loss scalar
    return t
