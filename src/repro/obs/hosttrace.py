"""Wall-clock spans and counters of the serving host.

The timeline tracer (:mod:`repro.obs.timeline`) times the cost model's
*modelled* clock.  This module times the host's real one: how long the
scheduler, the engine's dispatch and its charge path take in
wall-clock nanoseconds, where the collector paused, and which span was
open when XLA compiled.

Each :meth:`HostTrace.span`:

* emits a ``jax.profiler.TraceAnnotation`` under its own name, so that
  under a profiler the span lands on the host plane, on the same clock
  as the device trace (inert and about half a microsecond otherwise);
* appends ``(name, t0_ns, t1_ns, stats)`` from ``time.perf_counter_ns``
  to a bounded ring, in the order the spans close (``stats`` is None for
  a span without any: a record of plain numbers is one the collector
  stops tracking, so a full ring brings no full collections of its own);
* adds to per-name running totals (seconds and count).

Open spans form a stack, so that an event can name the innermost one.

Two process-wide hooks, registered once when the first ``HostTrace`` is
made, feed every live instance:

* ``gc.callbacks``: each collector pause becomes a ``host.gc`` span
  (stat ``generation``) and adds to ``gc_seconds[generation]``;
* ``jax.monitoring``'s backend-compile event: counted in ``compiles``
  and ``compile_seconds`` under the innermost open span ("" when none).

Spans never feed a decision: nothing on the charge path reads them, so
replay stays bit-identical.  Every clock read of the serving stack lives
here; charge-path modules only open spans by name.
"""

from __future__ import annotations

import gc
import time
import weakref
from collections import defaultdict, deque
from typing import Dict, List, Optional

import jax

__all__ = ["HostTrace", "RING_SPANS", "COMPILE_EVENT", "now_ns"]

# A 51-s window of the busiest chat traffic is about 1,700 decode steps
# at about 15 spans each, plus the admissions and the collector's
# pauses; the ring holds a few such windows (a record is ~150 B).
RING_SPANS = 1 << 17
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_annotation = jax.profiler.TraceAnnotation
now_ns = time.perf_counter_ns


class _Span:
    """One open span (a context manager; see :meth:`HostTrace.span`)."""

    __slots__ = ("ht", "name", "stats", "ann", "t0")

    def __init__(self, ht: "HostTrace", name: str, stats: dict):
        self.ht, self.name, self.stats = ht, name, stats

    def __enter__(self):
        self.ht.stack.append(self.name)
        self.ann = _annotation(self.name, **self.stats)
        self.ann.__enter__()
        self.t0 = now_ns()
        return self

    def __exit__(self, *exc):
        t1 = now_ns()
        self.ann.__exit__(None, None, None)
        self.ht.stack.pop()
        self.ht.record(self.name, self.t0, t1, self.stats)
        return False


class HostTrace:
    """Wall-clock spans in a bounded ring, with per-name totals."""

    def __init__(self):
        self.ring: deque = deque(maxlen=RING_SPANS)
        self.seconds: Dict[str, float] = defaultdict(float)
        self.counts: Dict[str, int] = defaultdict(int)
        self.gc_seconds: Dict[int, float] = defaultdict(float)
        self.compiles: Dict[str, int] = defaultdict(int)
        self.compile_seconds: Dict[str, float] = defaultdict(float)
        self.stack: List[str] = []
        # Records evicted from the ring: while 0, the ring is complete.
        self.dropped = 0
        _register(self)

    def span(self, name: str, **stats) -> _Span:
        """``with ht.span("decode.policy"): ...`` times the block."""
        return _Span(self, name, stats)

    def record(self, name: str, t0: int, t1: int, stats: dict) -> None:
        if len(self.ring) == self.ring.maxlen:
            self.dropped += 1
        self.ring.append((name, t0, t1, stats or None))
        self.seconds[name] += (t1 - t0) * 1e-9
        self.counts[name] += 1

    def innermost(self) -> str:
        return self.stack[-1] if self.stack else ""

    def wrapped_since(self, t_ns: int) -> bool:
        """Whether a record that closed at or after ``t_ns`` was evicted
        (the ring keeps close order, so its oldest record tells)."""
        return self.dropped > 0 and self.ring[0][2] >= t_ns

    def _on_gc(self, generation: int, t0: int, t1: int) -> None:
        self.record("host.gc", t0, t1, {"generation": generation})
        self.gc_seconds[generation] += (t1 - t0) * 1e-9

    def _on_compile(self, secs: float) -> None:
        inner = self.innermost()
        self.compiles[inner] += 1
        self.compile_seconds[inner] += secs


# ------------------------------------------------ process-wide hooks
# Live instances as weak references.  The list is replaced, never
# changed in place, so a hook that fires while it is being replaced
# (a collection can start inside any allocation) reads a whole list.
_live: List[weakref.ref] = []
_gc_open: List[Optional[object]] = [None, 0]     # [annotation, t0_ns]


def _instances():
    for ref in _live:
        ht = ref()
        if ht is not None:
            yield ht


def _on_gc(phase: str, info: dict) -> None:
    if phase == "start":
        ann = _annotation("host.gc", generation=info["generation"])
        ann.__enter__()
        _gc_open[0], _gc_open[1] = ann, now_ns()
        return
    ann, t0 = _gc_open
    if ann is None:           # registered mid-collection
        return
    t1 = now_ns()
    ann.__exit__(None, None, None)
    _gc_open[0] = None
    for ht in _instances():
        ht._on_gc(info["generation"], t0, t1)


def _on_event(name: str, secs: float, **_) -> None:
    if name == COMPILE_EVENT:
        for ht in _instances():
            ht._on_compile(secs)


def _register(ht: HostTrace) -> None:
    global _live
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)
        jax.monitoring.register_event_duration_secs_listener(_on_event)
    _live = [r for r in _live if r() is not None] + [weakref.ref(ht)]
