"""Sharded multi-device sweep engine: the (T, B) replay grid on a Mesh.

The multi-trace executor (``repro.core.replay``) vmaps one per-trace
program over a leading T trace axis with B policy lanes inside — a dense
(T, B) grid on ONE device.  This layer partitions that grid across a
2-D device mesh with axes ``("trace", "lane")``:

* the stacked :class:`~repro.traffic.plan.PlanBatch` arrays shard along
  T (``PartitionSpec("trace")``) — each device holds only its trace
  shard of the plan, so plans never replicate across the mesh;
* per-lane carries (net state, ready clocks, latency accumulators)
  shard along both axes (``P("trace", "lane")``); policy parameters
  shard along lanes only;
* the per-segment program is the SAME ``_make_run`` scan the
  single-device path jits, wrapped in ``shard_map`` — each device runs
  the identical step arithmetic on its (T/dt, B/db) tile, and there is
  no cross-device communication at all (the grid is embarrassingly
  parallel), so results are bit-identical to the vmapped engine and the
  serial oracle (``tests/test_shard_sweep.py``).

T and B rarely divide the mesh evenly: T pads with inert trace rows
(all-False participant mask, no messages, no barriers — provably no-op
steps) and B pads by repeating lane 0; both are sliced off at readback.
Placement (``jax.device_put`` with ``NamedSharding``) is cached per
(batch, mesh) in a small LRU — the device-local plan cache keyed by
(trace, topo) plan identity plus the mesh — so warm sweeps re-dispatch
into resident shards without host->device traffic, and compile counts
stay exactly one program per segment shape (placement itself compiles
nothing; ``baselines/compile_counts.json`` pins warm reruns at 0).

Enable explicitly (``use_mesh(...)`` / ``set_mesh``) or let
``auto_mesh`` pick a mesh whenever >1 device is visible and the grid is
big enough to tile.  ``sweep.sweep_cells`` consults this module, so the
tuner and suite runner go multi-device with no caller changes:

    XLA_FLAGS=--xla_force_host_platform_device_count=8  # or real chips
    with shard_sweep.use_mesh():
        tune_catalog(topo, ...)
"""
from __future__ import annotations

import math
from collections import OrderedDict
from contextlib import contextmanager
from functools import lru_cache, partial
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import replay
from repro.core.eee import Policy, PowerModel, canonical_proto
from repro.traffic.plan import PlanBatch

SP_TB = P("trace", "lane")
SP_B = P("lane")
SP_T = P("trace")

# ---------------------------------------------------------------------------
# Mesh selection
# ---------------------------------------------------------------------------


def _factor_pairs(n: int):
    for dt in range(1, n + 1):
        if n % dt == 0:
            yield dt, n // dt


def mesh_for(T: int, B: int, devices=None) -> Mesh:
    """Build the ("trace", "lane") mesh that tiles a (T, B) grid with the
    fewest padded cells.  Ties break toward more trace shards (trace rows
    carry the plan arrays, so splitting T first keeps per-device plan
    memory smallest)."""
    devices = jax.devices() if devices is None else list(devices)
    n = len(devices)

    def padded_cells(dt, db):
        return (math.ceil(T / dt) * dt) * (math.ceil(B / db) * db) - T * B

    dt, db = min(_factor_pairs(n),
                 key=lambda p: (padded_cells(*p), p[1]))
    return Mesh(np.asarray(devices).reshape(dt, db), ("trace", "lane"))


_ACTIVE_MESH: Optional[Mesh] = None
_AUTO = False


def set_mesh(mesh: Optional[Mesh], auto: bool = False) -> None:
    """Install the mesh ``sweep_cells`` dispatches onto (None disables).
    ``auto=True`` (with ``mesh=None``) re-derives a best-fit mesh per
    grid shape from all visible devices."""
    global _ACTIVE_MESH, _AUTO
    _ACTIVE_MESH, _AUTO = mesh, auto


@contextmanager
def use_mesh(mesh: Optional[Mesh] = None):
    """Scoped ``set_mesh``: an explicit mesh, or auto mode when None."""
    prev = (_ACTIVE_MESH, _AUTO)
    set_mesh(mesh, auto=mesh is None)
    try:
        yield
    finally:
        set_mesh(*prev)


def active_mesh(T: int, B: int) -> Optional[Mesh]:
    """The mesh a (T, B) grid should run on right now, or None for the
    single-device path.  Auto mode only engages when sharding can help:
    >1 device and at least one grid cell per device."""
    if _ACTIVE_MESH is not None:
        return _ACTIVE_MESH
    if _AUTO and jax.device_count() > 1 and T * B >= jax.device_count():
        return mesh_for(T, B)
    return None


# ---------------------------------------------------------------------------
# Device-local plan placement (the per-(trace, topo, shard) plan cache)
# ---------------------------------------------------------------------------

# (id(batch), mesh, T_pad) -> (batch strong ref, part_mask, [segment xs])
_PLACED: "OrderedDict[tuple, tuple]" = OrderedDict()
_PLACED_MAX = 16
_PLACED_STATS = {"hits": 0, "misses": 0}


def _pad_T(v: jnp.ndarray, T_pad: int, fill=0):
    extra = T_pad - v.shape[0]
    if extra <= 0:
        return v
    pad = jnp.full((extra,) + v.shape[1:], fill, v.dtype)
    return jnp.concatenate([v, pad])


def _place_batch(batch: PlanBatch, mesh: Mesh, T_pad: int):
    """Shard ``batch``'s arrays along the trace axis, padding T with inert
    rows (no participants, no messages, no barriers — every padded step
    lowers to the executor's cond-false / no-op branches).  Cached per
    (batch, mesh): each device keeps only its own trace shard resident,
    and warm sweeps skip the host->device placement entirely."""
    key = (id(batch), mesh, T_pad)
    hit = _PLACED.get(key)
    if hit is not None and hit[0] is batch:
        _PLACED_STATS["hits"] += 1
        _PLACED.move_to_end(key)
        return hit[1], hit[2]
    _PLACED_STATS["misses"] += 1

    def put_T(v, fill=0):
        return jax.device_put(_pad_T(v, T_pad, fill),
                              NamedSharding(mesh, SP_T))

    part_mask = put_T(batch.part_mask)
    seg_xs = [{k: put_T(v, -1 if k == "links" else 0)
               for k, v in seg.xs.items()} for seg in batch.segments]
    _PLACED[key] = (batch, part_mask, seg_xs)
    while len(_PLACED) > _PLACED_MAX:
        _PLACED.popitem(last=False)
    return part_mask, seg_xs


def placement_cache_clear() -> None:
    _PLACED.clear()
    for k in _PLACED_STATS:
        _PLACED_STATS[k] = 0


def placement_cache_info() -> dict:
    return {"placed": len(_PLACED), **_PLACED_STATS}


# ---------------------------------------------------------------------------
# The sharded per-segment runner
# ---------------------------------------------------------------------------


@lru_cache(maxsize=None)
def _sharded_runner(proto: Policy, pm: PowerModel, n_links: int, cap: int,
                    mesh: Mesh, mode: str = "scan",
                    needs_sort: bool = True):
    """``_make_run`` vmapped over T and shard_mapped over the mesh — the
    same scan program as ``replay._multi_segment_runner``, tiled.  Every
    input/output is tile-local (``check_vma=False``: there is no
    replication to verify and no collective in the program).  Carry
    buffers donate, exactly like the single-device runners."""
    run = replay._make_run(proto, pm, n_links, cap, collect_events=False,
                           mode=mode, needs_sort=needs_sort)
    vrun = jax.vmap(run, in_axes=(0, None, 0, 0, 0, 0, 0))
    sm = jax.shard_map(vrun, mesh=mesh,
                       in_specs=(SP_TB, SP_B, SP_TB, SP_TB, SP_TB, SP_T,
                                 SP_T),
                       out_specs=(SP_TB, None), check_vma=False)
    return partial(jax.jit, donate_argnums=(0, 2, 3, 4))(sm)


def _pad_pols(pols: List[Policy], B_pad: int) -> List[Policy]:
    return list(pols) + [pols[0]] * (B_pad - len(pols))


def replay_plans_sharded(batch: PlanBatch, pols, pm: PowerModel,
                         mesh: Optional[Mesh] = None):
    """Sharded twin of :func:`repro.core.replay.replay_plans` — same
    signature plus ``mesh``, same ``(nets, t_end, lat_sum, lat_max)``
    return contract, bit-identical per-cell results.

    Falls back to the single-device engine when the mesh is trivial
    (1 device) so callers can pass whatever ``active_mesh`` returned.
    """
    T, B = batch.n_traces, len(pols)
    if mesh is None:
        mesh = active_mesh(T, B)
    if mesh is None or mesh.devices.size <= 1:
        return replay.replay_plans(batch, pols, pm)

    dt, db = mesh.shape["trace"], mesh.shape["lane"]
    T_pad = math.ceil(T / dt) * dt
    B_pad = math.ceil(B / db) * db

    proto = canonical_proto(pols[0])
    params = replay.stack_params(_pad_pols(pols, B_pad))
    carry = replay._multi_init(proto, batch.n_links, batch.n_nodes,
                               T_pad)(params)

    def put(x, spec):
        return jax.device_put(x, NamedSharding(mesh, spec))

    params = jax.tree.map(lambda x: put(x, SP_B), params)
    carry = (jax.tree.map(lambda x: put(x, SP_TB), carry[0]),
             put(carry[1], SP_TB), put(carry[2], SP_TB),
             put(carry[3], SP_TB))
    part_mask, seg_xs = _place_batch(batch, mesh, T_pad)

    for seg, xs in zip(batch.segments, seg_xs):
        md, ns = replay._seg_flags(seg, proto)
        run = _sharded_runner(proto, pm, batch.n_links, seg.cap, mesh,
                              md, ns)
        carry, _ = run(carry[0], params, carry[1], carry[2], carry[3],
                       part_mask, xs)
    nets, ready, lat_sum, lat_max = carry

    t_end = np.asarray(replay._participant_max_multi(part_mask, ready))
    t_end = np.where(batch.has_participants[:, None], t_end[:T, :B], 0.0)
    nets = jax.tree.map(lambda x: x[:T, :B], nets)
    return (nets, t_end, np.asarray(lat_sum)[:T, :B],
            np.asarray(lat_max)[:T, :B])
