"""Declarative scenario specs: named, parametric, reproducible workloads.

A :class:`Scenario` is a frozen value describing HOW to synthesize a
:class:`~repro.traffic.trace.Trace` — a builder name, a node count, a seed
and a parameter tuple — without holding the trace itself.  Specs hash, so
they key caches and registries, travel through configs, and scale
(``scaled``) without touching builder code.

Builders are plain functions ``fn(topo, n_nodes, seed, **params) -> Trace``
registered under a string key with :func:`builder`; keeping the spec ->
builder indirection declarative means a catalog entry is data, not code.

``build_trace`` memoizes the synthesized Trace per (spec, topology) in a
bounded LRU.  That identity-stability is load-bearing: the trace-plan cache
(``repro.traffic.plan``) keys on trace identity, so every suite run, sweep
group and warm benchmark pass of a scenario hits ONE compiled plan — the
"plan cache keyed per scenario" contract.  Any RNG a builder uses must be
derived from ``seed`` (``rng(seed)`` below — counter-based Philox, stable
across platforms); the replay hot path itself never sees host RNG because
synthesis happens once, before planning.
"""
from __future__ import annotations

import dataclasses
from collections import OrderedDict
from dataclasses import dataclass
from typing import Callable, Dict

import numpy as np

_BUILDERS: Dict[str, Callable] = {}


def builder(name: str):
    """Register a trace builder ``fn(topo, n_nodes, seed, **params)``."""
    def deco(fn):
        assert name not in _BUILDERS, f"duplicate builder {name!r}"
        _BUILDERS[name] = fn
        return fn
    return deco


def builder_names() -> list:
    return sorted(_BUILDERS)


def rng(seed: int) -> np.random.Generator:
    """The scenario RNG: counter-based Philox, so a (seed, draw-sequence)
    pair reproduces bit-identically across platforms and numpy versions."""
    return np.random.Generator(np.random.Philox(seed))


def params_of(**kw) -> tuple:
    """Normalize builder kwargs into the spec's hashable params tuple."""
    return tuple(sorted(kw.items()))


@dataclass(frozen=True)
class Scenario:
    """One named, parametric workload (a catalog entry).

    ``family`` groups catalog listings: ``ml`` (training phases from
    ``scenarios.models``), ``hpc`` (stencil / BSP iteration structures),
    ``dc`` (stochastic datacenter arrivals), ``app`` (the paper's §4
    application generators).
    """
    name: str
    family: str                  # ml | hpc | dc | app
    builder: str
    n_nodes: int
    seed: int = 0
    params: tuple = ()           # sorted (key, value) pairs, see params_of
    description: str = ""

    def scaled(self, n_nodes: int, seed: int | None = None) -> "Scenario":
        """The same scenario on a different allocation size (and optionally
        a different seed) — builders auto-derive internal shape (e.g. the
        DP/TP/PP grid) from ``n_nodes``."""
        return dataclasses.replace(
            self, n_nodes=n_nodes,
            seed=self.seed if seed is None else seed)

    def build(self, topo):
        return build_trace(self, topo)


# -- per-(spec, topology) trace memo ----------------------------------------
# Identity-stable traces keep the downstream plan cache hot; bounded so a
# long-running catalog sweep cannot grow host memory without limit.
_TRACE_CACHE: OrderedDict = OrderedDict()
_TRACE_CACHE_MAX = 64


def build_trace(spec: Scenario, topo):
    """Synthesize (or fetch the cached) Trace for a scenario on a topology."""
    if spec.builder not in _BUILDERS:
        raise KeyError(f"unknown builder {spec.builder!r}; "
                       f"have {builder_names()}")
    key = (spec, topo)
    hit = _TRACE_CACHE.get(key)
    if hit is not None:
        _TRACE_CACHE.move_to_end(key)
        return hit
    tr = _BUILDERS[spec.builder](topo, n_nodes=spec.n_nodes, seed=spec.seed,
                                 **dict(spec.params))
    tr.name = spec.name
    _TRACE_CACHE[key] = tr
    while len(_TRACE_CACHE) > _TRACE_CACHE_MAX:
        _TRACE_CACHE.popitem(last=False)
    return tr


def trace_cache_clear() -> None:
    _TRACE_CACHE.clear()
