"""Lightweight JAX instrumentation: compile counting, host spans, counters.

``count_compiles()`` taps ``jax.monitoring`` for backend-compile events
(and persistent-cache hits) so the benchmark driver and ``chip_smoke.py``
can report how many XLA programs a run built and how long that took (the
perf-trajectory JSON in ``benchmarks/run.py``) and the test-suite can
assert that warm plan replays compile NOTHING.  ``compile_guard()`` turns
that assertion into a hard runtime error for regions that MUST stay
program-cache-hot (warm auto-tuner rounds, warm bench passes).  Transfer
elimination is pinned separately with ``jax.transfer_guard`` (see
tests/test_plan.py).

``span(name, **attrs)`` marks one layer's work on the host.  It always
opens a ``jax.profiler.TraceAnnotation``, so the span lands in a profiler
trace beside the device's programs (and costs about a microsecond when no
trace is taken).  Inside ``span_log()`` it also keeps
``(name, start_ns, end_ns, parent)`` in the caller's log, where ``add``
sums counters; with no log open nothing is kept.  The log is per caller
(a ``contextvars`` variable): the innermost ``span_log`` of the running
thread or task receives the spans and counters.
"""
from __future__ import annotations

import contextlib
import contextvars
import time
from typing import NamedTuple, Optional

import jax.monitoring
import jax.profiler

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


# ---------------------------------------------------------------------------
# Host spans and counters
# ---------------------------------------------------------------------------


class Span(NamedTuple):
    name: str
    start_ns: int
    end_ns: int
    parent: Optional[int]        # index of the enclosing span in the log


class SpanLog:
    """The spans and counters recorded inside one ``span_log()``."""

    def __init__(self):
        self.spans: list = []        # Span per closed span, None while open
        self.counts: dict = {}
        self._open: list = []

    def ms(self, *names: str) -> float:
        """Summed wall milliseconds of the closed spans named ``names``."""
        return 1e-6 * sum(s.end_ns - s.start_ns for s in self.spans
                          if s is not None and s.name in names)


_LOG: contextvars.ContextVar = contextvars.ContextVar("repro_span_log",
                                                      default=None)


@contextlib.contextmanager
def span_log():
    """Context manager yielding a fresh :class:`SpanLog` that receives the
    spans and counters of this caller until it closes:

        with span_log() as log:
            ...
        print(log.ms("repro.replay.wait"), log.counts)
    """
    log = SpanLog()
    token = _LOG.set(log)
    try:
        yield log
    finally:
        _LOG.reset(token)


class span:
    """Context manager: one host span, in the profiler's trace always and
    in the open ``span_log`` if there is one."""

    __slots__ = ("_ann", "_name", "_log", "_i", "_parent", "_start")

    def __init__(self, name: str, **attrs):
        self._ann = jax.profiler.TraceAnnotation(name, **attrs)
        self._name = name

    def __enter__(self):
        self._ann.__enter__()
        log = self._log = _LOG.get()
        if log is not None:
            # the slot is taken now so that nested spans can name it
            self._i = len(log.spans)
            self._parent = log._open[-1] if log._open else None
            log.spans.append(None)
            log._open.append(self._i)
            self._start = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        log = self._log
        if log is not None:
            end = time.perf_counter_ns()
            log._open.pop()
            log.spans[self._i] = Span(self._name, self._start, end,
                                      self._parent)
        self._ann.__exit__(*exc)
        return False


def add(name: str, n) -> None:
    """Add ``n`` to the counter ``name`` of the open ``span_log`` (nothing
    when none is open)."""
    log = _LOG.get()
    if log is not None:
        log.counts[name] = log.counts.get(name, 0) + n
