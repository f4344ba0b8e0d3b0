"""Structured tracing: typed events with engine-tick + wall timestamps.

Two implementations share one interface:

* :class:`Tracer` — records events in memory for the exporters
  (``obs.export``: JSONL + Chrome trace-event/Perfetto) and the span
  validator (``obs.validate``).
* :class:`NullTracer` — the module-level :data:`NULL_TRACER` singleton the
  engine holds when tracing is off. Every method is a no-op and
  ``enabled`` is False, so hot emission sites guard with
  ``if tracer.enabled:`` and pay one attribute read + branch per *site*
  (not per event) — a guarded site builds no kwargs dict on the disabled
  path. ``NullTracer.span`` hands back one shared no-op context: the
  per-step sites (``data.batch``, ``step.dispatch``) pass no fields, so
  each costs an attribute read, a call and the ``with``; the once-a-gang
  sites (``gang``, ``rung``) also build their fields' dict.

Event taxonomy (the ``ev`` field):

Per-request lifecycle (all carry ``rid``):
  ``enqueue`` → ``admit`` (cell ``k,m,b``; ``prefix_hit`` rides alongside
  when admission matched cached blocks) → ``prefill_chunk``* →
  ``first_token`` → [``retract`` (``via`` = swap|recompute) →
  ``swap_out`` → ``restore``]* → [``spec_propose`` → ``spec_verify`` →
  ``rollback``]* → ``complete``.

Per-round engine records: ``round`` — call-mode mix, mixed-wave fill,
pool blocks in use, per-partition host-tier depth, transfer in-flight
peak, per-arch queue depths, slot occupancy.

Subsystem instants: ``prefix_spill`` / ``prefix_evict`` /
``host_evict`` (tiered store + radix cache), ``compile`` (first sight of
a (mode, token shape, table bucket) pipeline-program signature in one
engine).

Spans (:meth:`Tracer.span`): ``span_begin`` / ``span_end`` pairs with a
per-tracer ``id`` and the enclosing span's id as ``parent``. The training
path opens ``rung`` and ``gang`` (``core.hydra``), ``build.params`` /
``build.optimizer`` / ``build.step`` (the gang's set-up), ``data.batch``
(``TrainBatches.batch_for_step``) and ``step.dispatch`` (the call into
the jitted train step). Each span is also a
``jax.profiler.TraceAnnotation`` of the same name, so while a profiler
runs it lands in the profiler's trace on the trace's own clock.

``xla_compile``: one per XLA backend compile (or load from JAX's
persistent cache) that happens while a span of this tracer is open on
the compiling thread, from ``jax.monitoring``: ``program`` (JAX's name
for it, e.g. ``jit(train_step)``), ``seconds``, ``cache_hit`` (the
executable came from the persistent cache) and ``span`` (the name of the
innermost open span).

Timestamps: ``tick`` is the engine round (set once per round via
:meth:`begin_tick`; emission sites never thread it), ``wall`` is seconds
since the tracer was constructed, from ``time.perf_counter_ns``. Spans
are wall-only (``tick`` = -1 outside an engine round). :meth:`anchor`
ties ``wall`` to a running profiler's clock.
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import Optional

import jax

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_READ = "/jax/compilation_cache/cache_retrieval_time_sec"

_thread = threading.local()
_listening = False


def _active() -> list:
    """The tracers with a span open on this thread, innermost last: the
    compile listener reports to the last one."""
    stack = getattr(_thread, "tracers", None)
    if stack is None:
        stack = _thread.tracers = []
    return stack


def _on_duration(event: str, seconds: float, **kw) -> None:
    stack = _active()
    if not stack:
        return
    tr = stack[-1]
    if event == _CACHE_READ:
        tr._cache_read = True
    elif event == _BACKEND_COMPILE:
        tr.emit("xla_compile", program=kw.get("fun_name"), seconds=seconds,
                cache_hit=tr._cache_read, span=tr._spans[-1][1])
        tr._cache_read = False


def _listen() -> None:
    """Register the one ``jax.monitoring`` listener (listeners cannot be
    removed, so it is registered once per process)."""
    global _listening
    if not _listening:
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _listening = True


class Tracer:
    """In-memory structured event recorder. See the module docstring for
    the event taxonomy; exporters live in ``obs.export``."""

    enabled = True

    def __init__(self):
        self.events: list = []
        self.tick = -1  # current engine round; -1 = outside any round
        self._t0 = time.perf_counter_ns()
        self._spans: list = []  # (id, name) of the open spans, innermost last
        self._next_id = 0
        self._cache_read = False
        _listen()

    # -- timestamps ----------------------------------------------------------

    def begin_tick(self, tick: int) -> None:
        """Set the engine-tick timestamp for every event until the next
        round (so per-event emission never threads the tick)."""
        self.tick = tick

    def _wall(self) -> float:
        return (time.perf_counter_ns() - self._t0) / 1e9

    def anchor(self) -> None:
        """Write an ``obs.clock`` annotation that carries this tracer's
        ``wall`` at its start into a running profiler trace. An event
        stamped ``wall`` then sits at ``start + (wall - anchor_wall)`` on
        the trace's clock, in seconds: set-up spans and compiles recorded
        before the profiler started line up with the device's time. Call
        it right after ``jax.profiler.start_trace``."""
        with jax.profiler.TraceAnnotation("obs.clock", wall=self._wall()):
            pass

    # -- emission ------------------------------------------------------------

    def emit(self, ev: str, **fields) -> None:
        fields["ev"] = ev
        fields["tick"] = self.tick
        fields["wall"] = round(self._wall(), 6)
        self.events.append(fields)

    def req(self, ev: str, rid: int, **fields) -> None:
        """Per-request lifecycle event."""
        self.emit(ev, rid=rid, **fields)

    def round(self, **fields) -> None:
        """Per-round engine record (one per engine tick while tracing)."""
        self.emit("round", **fields)

    def compile(self, mode: str, **fields) -> None:
        """First sight of a pipeline-program shape signature in one serve
        engine. It is not an XLA compile: JAX's caches may already hold
        that program. Real compiles are the ``xla_compile`` events."""
        self.emit("compile", mode=mode, **fields)

    @contextlib.contextmanager
    def span(self, name: str, **fields):
        """Record ``name`` as a ``span_begin``/``span_end`` pair around the
        block (with ``fields`` on the begin event), nested under the
        innermost open span, and as a profiler annotation of that name."""
        sid = self._next_id
        self._next_id += 1
        parent = self._spans[-1][0] if self._spans else None
        self.emit("span_begin", name=name, id=sid, parent=parent, **fields)
        self._spans.append((sid, name))
        active = _active()
        active.append(self)
        try:
            with jax.profiler.TraceAnnotation(name):
                yield sid
        finally:
            active.pop()
            self._spans.pop()
            self.emit("span_end", name=name, id=sid)

    # -- management ----------------------------------------------------------

    def clear(self) -> None:
        self.events = []
        self.tick = -1
        self._t0 = time.perf_counter_ns()

    def __len__(self) -> int:
        return len(self.events)


_NULL_SPAN = contextlib.nullcontext()


class NullTracer:
    """The disabled path: ``enabled`` False, every method a no-op. Hot
    sites guard event construction with ``if tracer.enabled:`` so the only
    per-round cost when tracing is off is the attribute read + branch."""

    enabled = False
    events: list = []  # always empty; shared on purpose (never appended)
    tick = -1

    def begin_tick(self, tick: int) -> None:
        pass

    def anchor(self) -> None:
        pass

    def emit(self, ev: str, **fields) -> None:
        pass

    def req(self, ev: str, rid: int, **fields) -> None:
        pass

    def round(self, **fields) -> None:
        pass

    def compile(self, mode: str, **fields) -> None:
        pass

    def span(self, name: str, **fields):
        return _NULL_SPAN

    def clear(self) -> None:
        pass

    def __len__(self) -> int:
        return 0


NULL_TRACER = NullTracer()


def resolve(tracer: Optional[Tracer]):
    """``tracer or NULL_TRACER`` with the None-vs-disabled distinction kept
    explicit at construction sites."""
    return tracer if tracer is not None else NULL_TRACER


__all__ = ["Tracer", "NullTracer", "NULL_TRACER", "resolve"]
