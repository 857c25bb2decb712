"""Lightweight JAX instrumentation: compile counting for benches + tests.

``count_compiles()`` taps ``jax.monitoring`` for backend-compile events
(and persistent-cache hits) so the benchmark driver and ``chip_smoke.py``
can report how many XLA programs a run built and how long that took (the
perf-trajectory JSON in ``benchmarks/run.py``) and the test-suite can
assert that warm plan replays compile NOTHING.  ``compile_guard()`` turns
that assertion into a hard runtime error for regions that MUST stay
program-cache-hot (warm auto-tuner rounds, warm bench passes).  Transfer
elimination is pinned separately with ``jax.transfer_guard`` (see
tests/test_plan.py).
"""
from __future__ import annotations

import contextlib

import jax.monitoring

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_state = {"installed": False, "n": 0, "secs": 0.0, "hits": 0}


def _on_duration(event, duration, **_kw):
    if event == _COMPILE_EVENT:
        _state["n"] += 1
        _state["secs"] += duration


def _on_event(event, **_kw):
    if event == _CACHE_HIT_EVENT:
        _state["hits"] += 1


def _install():
    if not _state["installed"]:
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)
        _state["installed"] = True


class CompileCount:
    def __init__(self):
        self._start = dict(_state)

    def _delta(self, key):
        return _state[key] - self._start[key]

    @property
    def count(self) -> int:
        """Programs that went through the backend compile step: built by
        XLA, or read back from the persistent compilation cache."""
        return self._delta("n")

    @property
    def seconds(self) -> float:
        """Wall seconds spent in that step (compiling or cache reads)."""
        return self._delta("secs")

    @property
    def cache_hits(self) -> int:
        """Of ``count``, the programs read from the persistent cache."""
        return self._delta("hits")


@contextlib.contextmanager
def count_compiles():
    """Context manager yielding a live backend-compile counter:

        with count_compiles() as cc:
            ...
        print(cc.count, cc.seconds, cc.cache_hits)
    """
    _install()
    yield CompileCount()


class CompileGuardError(RuntimeError):
    """A guarded region built more XLA programs than its budget allows."""


@contextlib.contextmanager
def compile_guard(what: str = "guarded region", budget: int = 0):
    """Fail loudly when a region compiles more than ``budget`` programs.

    The hard-error sibling of ``count_compiles`` for code paths whose whole
    point is program-cache reuse: warm replay loops, the auto-tuner's warm
    refinement rounds.  Yields the live counter so callers can also record
    the observed count.
    """
    with count_compiles() as cc:
        yield cc
    if cc.count > budget:
        raise CompileGuardError(
            f"{what} compiled {cc.count} XLA programs "
            f"(budget {budget}) — a plan/program cache went cold")
