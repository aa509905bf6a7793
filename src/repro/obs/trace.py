"""Lightweight span/counter tracing over a host-side ring buffer.

Design constraints (ISSUE 9 acceptance):

  * ZERO cost when disabled — the module-level helpers check one global
    and return a shared no-op; no event objects, no clock reads, and
    never any device ops (instrumentation sites only touch host state
    and trace-time static facts like ``eval_shape`` structs);
  * bounded memory when enabled — a ``deque(maxlen=capacity)`` ring
    buffer drops the OLDEST events and counts the drops, so a long run
    can leave tracing on without growing without bound;
  * flat events — one :class:`TraceEvent` record maps 1:1 onto the
    JSONL schema (obs/export.py);
  * one clock with the device — when enabled, each span also opens a
    ``jax.profiler.TraceAnnotation`` of its name, so a profiler capture
    (``--profile DIR`` in the launchers) shows the program's spans on the
    host timeline beside the device ops.

:func:`scope` names a function's ops inside the compiled program
(``jax.named_scope``): metadata only, no run-time cost, on or off.

Usage::

    from repro.obs import trace
    tracer = trace.enable()
    with trace.span("train.step", cat="train", step=3):
        ...                       # timed wall-clock span
    trace.counter("queue", cat="serve", depth=4)
    trace.instant("policy.resolved", cat="policy", name="q8")
    events = tracer.drain()

Timestamps are seconds since the tracer's epoch (``perf_counter`` based,
monotonic); exporters convert to microseconds.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import time
from collections import deque
from typing import Deque, List, Optional

import jax

# Event phases: X = complete span (ts + dur), C = counter sample,
# i = instant event.
PHASES = ("X", "C", "i")


@dataclasses.dataclass
class TraceEvent:
    """One telemetry record: a span, counter sample or instant marker."""
    name: str
    cat: str
    ph: str                      # one of PHASES
    ts: float                    # seconds since tracer epoch
    dur: float = 0.0             # seconds (spans only)
    args: dict = dataclasses.field(default_factory=dict)

    def to_dict(self) -> dict:
        return {"name": self.name, "cat": self.cat, "ph": self.ph,
                "ts_us": round(self.ts * 1e6, 1),
                "dur_us": round(self.dur * 1e6, 1), "args": self.args}


class Tracer:
    """Host-side ring buffer of :class:`TraceEvent` records."""

    def __init__(self, capacity: int = 65536):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        self.events: Deque[TraceEvent] = deque(maxlen=capacity)
        self.dropped = 0
        self.t0 = time.perf_counter()

    def _now(self) -> float:
        return time.perf_counter() - self.t0

    def _append(self, ev: TraceEvent) -> None:
        if len(self.events) == self.capacity:
            self.dropped += 1
        self.events.append(ev)

    # -- emit ---------------------------------------------------------------

    def instant(self, name: str, cat: str = "default", **args) -> None:
        self._append(TraceEvent(name, cat, "i", self._now(), 0.0, args))

    def counter(self, name: str, cat: str = "default", **values) -> None:
        """A counter sample: ``values`` are the tracked numeric series."""
        self._append(TraceEvent(name, cat, "C", self._now(), 0.0, values))

    @contextlib.contextmanager
    def span(self, name: str, cat: str = "default", **args):
        """Wall-clock a ``with`` block as one complete ("X") event.

        Yields the event's mutable ``args`` dict so the block can attach
        results it only knows at the end (e.g. a loss value).  The block
        also runs under a profiler annotation of the same name."""
        t0 = self._now()
        try:
            with jax.profiler.TraceAnnotation(name):
                yield args
        finally:
            self._append(TraceEvent(name, cat, "X", t0,
                                    self._now() - t0, args))

    # -- read ---------------------------------------------------------------

    def drain(self) -> List[TraceEvent]:
        """Pop and return every buffered event (oldest first)."""
        out = list(self.events)
        self.events.clear()
        return out

    def snapshot(self) -> List[TraceEvent]:
        """Buffered events without clearing (oldest first)."""
        return list(self.events)

    def stats(self) -> dict:
        return {"buffered": len(self.events), "dropped": self.dropped,
                "capacity": self.capacity}


# ---------------------------------------------------------------------------
# Global tracer: default-off; the module helpers are the instrumentation
# surface (one global check, a shared nullcontext when disabled)
# ---------------------------------------------------------------------------

_TRACER: Optional[Tracer] = None
_NULL = contextlib.nullcontext({})


def enable(capacity: int = 65536) -> Tracer:
    """Install (and return) the global tracer; idempotent per-process
    enablement replaces any previous tracer."""
    global _TRACER
    _TRACER = Tracer(capacity)
    return _TRACER


def disable() -> None:
    global _TRACER
    _TRACER = None


def get_tracer() -> Optional[Tracer]:
    """The active tracer, or None when tracing is off (the default)."""
    return _TRACER


def span(name: str, cat: str = "default", **args):
    """Module-level span: a real timed span when tracing is enabled, a
    shared no-op context (no clock read, no allocation) otherwise."""
    t = _TRACER
    return t.span(name, cat, **args) if t is not None else _NULL


def instant(name: str, cat: str = "default", **args) -> None:
    t = _TRACER
    if t is not None:
        t.instant(name, cat, **args)


def counter(name: str, cat: str = "default", **values) -> None:
    t = _TRACER
    if t is not None:
        t.counter(name, cat, **values)


def scope(name: str):
    """Decorator: run the function under ``jax.named_scope(name)``.

    A fresh scope per call: ``jax.named_scope`` used as a decorator keeps
    one context object, whose saved state a nested or concurrent trace of
    the same function would overwrite.  The name lands in the ``op_name``
    metadata of every HLO op the function emits, in the forward pass, its
    transpose and remat's recompute alike; it adds no op.  The names in
    use (bench/scopes.py reads them): ``vocab``, ``layers``, ``attn``,
    ``mlp``, ``norm``, ``codec``, ``optimizer``; and one per ring of the
    mesh, ``stage_wire`` (a pipeline stage hop, transport/pipeline.py)
    and ``tensor_wire`` (a tensor-parallel gather or scatter,
    transport/tp_collectives.py)."""
    def wrap(fn):
        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return scoped
    return wrap
