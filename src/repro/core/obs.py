"""Observability substrate: trace events, spans, counters + Chrome export.

This module is the layer-neutral half of the tracing subsystem (the
serving-specific per-request flight recorder lives in
``serving/trace.py``).  Core modules — the controller, the admission
controller, the cluster simulator — accept an optional :class:`Tracer`
and emit *instant* or *span* events onto a shared timeline; the serving
layer subclasses it to accrue per-request span timelines with a
conservation invariant, and the paged runtime records the phases of
each step as nested spans and its work as counters.

Design contract (the reason this file exists at all):

* **A tracer reads no clock unless it is given one.**  Without a
  ``clock`` every timestamp is caller-provided (the harness's virtual
  clock): tracing never perturbs virtual time, and a trace recorded
  under it replays bit-identically.  With a ``clock`` (a wall-clock
  harness passes its own run clock), :meth:`Tracer.scope` stamps spans
  from it, so the program's spans and the harness's stamps share one
  timeline; each scope also enters ``jax.profiler.TraceAnnotation``, so
  the same span lands on the profiler's host plane, on the device
  trace's clock.
* **Disabled tracing is free.**  Every call site is guarded
  (``if tracer is not None``): with no tracer attached, no event,
  counter or annotation is created.
* **Export is Chrome ``trace_event`` JSON** (the format Perfetto /
  ``chrome://tracing`` load directly): tracks map to pids, lanes to
  tids, seconds to microseconds.
"""
from __future__ import annotations

import contextlib
import json
import time
import weakref
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterator, List, Optional


@dataclass
class TraceEvent:
    """One event on the shared timeline.

    ``ph`` follows the trace_event phase vocabulary: ``"X"`` is a
    complete span (``ts`` + ``dur``), ``"i"`` an instant.  ``track``
    groups events into a Perfetto process row (a tenant, or the
    ``"controller"`` track all actuator/controller events share);
    ``lane`` is the thread row within it (a request id, an actor name).
    Spans opened by :meth:`Tracer.scope` carry an ``id`` and the id of
    the scope they were opened in (``parent``; None at the top).
    """
    name: str
    ph: str                       # "X" complete span | "i" instant
    ts: float                     # seconds on the tracer's timeline
    dur: float = 0.0              # seconds ("X" only)
    track: str = ""
    lane: str = ""
    args: Dict[str, Any] = field(default_factory=dict)
    id: Optional[int] = None
    parent: Optional[int] = None


class Stopwatch:
    """Times one block at a time and records nothing: what a timed call
    site uses when no tracer is attached.  ``dur`` has the seconds of
    the last block, as a :meth:`Tracer.scope` event has its own."""

    def __init__(self) -> None:
        self.dur = 0.0
        self._t0 = 0.0

    def __enter__(self) -> "Stopwatch":
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc: Any) -> None:
        self.dur = time.perf_counter() - self._t0


# jax.monitoring's listeners are process-wide and are never dropped, so
# one listener, registered on first use, counts for every tracer that
# has an attachment open, and holds none of them alive
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compile_watchers: "weakref.WeakKeyDictionary[Tracer, int]" = \
    weakref.WeakKeyDictionary()
_listening = False


def _on_duration(event: str, duration: float, **kwargs: Any) -> None:
    if event == _COMPILE_EVENT:
        for tracer in list(_compile_watchers):
            tracer.count("compiles")


class Tracer:
    """Event collector every instrumented layer can write to.

    The base class just accumulates :class:`TraceEvent` objects —
    enough for the controller/actuator/admission call sites, the
    actuator lint test, and the e5 pause-correlation analysis.  The
    serving flight recorder (``serving/trace.py``) extends it with
    per-request timelines and retention policy.

    ``actions`` additionally indexes every :meth:`action` event (the
    controller-plane subset) so request timelines can be checked for
    overlap with reconfigure pause windows without scanning the full
    event list.

    ``clock`` (a callable returning seconds) is what :meth:`scope`
    stamps spans with; None (the default) keeps every stamp
    caller-provided and :meth:`scope` unavailable.  ``counters`` holds
    what :meth:`count` counted.
    """

    def __init__(self, clock: Optional[Callable[[], float]] = None) -> None:
        self.events: List[TraceEvent] = []
        self.actions: List[TraceEvent] = []
        self.clock = clock
        self.counters: Dict[str, int] = {}
        self._open: List[TraceEvent] = []     # scopes entered, innermost last
        self._next_id = 0

    # ------------------------------------------------------------ emission
    def instant(self, name: str, t: float, track: str = "",
                lane: str = "", **args: Any) -> TraceEvent:
        ev = TraceEvent(name, "i", t, 0.0, track, lane, args)
        self.events.append(ev)
        return ev

    def span(self, name: str, t0: float, t1: float, track: str = "",
             lane: str = "", **args: Any) -> TraceEvent:
        ev = TraceEvent(name, "X", t0, max(0.0, t1 - t0), track, lane, args)
        self.events.append(ev)
        return ev

    def action(self, name: str, t: float, tenant: str, dur: float = 0.0,
               **args: Any) -> TraceEvent:
        """A controller/actuator action.  ``dur > 0`` records the pause
        window it imposes (a MIG reconfigure's re-lower, a move) as a
        span on the shared ``controller`` track; instantaneous knob
        turns (io throttle, MPS quota) land as instants."""
        args = {"tenant": tenant, **args}
        if dur > 0:
            ev = self.span(name, t, t + dur, track="controller",
                           lane=tenant, **args)
        else:
            ev = self.instant(name, t, track="controller", lane=tenant,
                              **args)
        self.actions.append(ev)
        return ev

    @contextlib.contextmanager
    def scope(self, name: str, **args: Any) -> Iterator[TraceEvent]:
        """Record the enclosed block as an ``"X"`` span on the tracer's
        clock (track ``host``), its ``parent`` the scope it was opened
        in, and enter ``jax.profiler.TraceAnnotation(name)`` around it.
        Yields the event, whose ``dur`` is set when the block exits."""
        if self.clock is None:
            raise ValueError("Tracer.scope needs a tracer built with a clock")
        from jax.profiler import TraceAnnotation
        ev = TraceEvent(name, "X", self.clock(), 0.0, "host", "", args,
                        id=self._next_id,
                        parent=self._open[-1].id if self._open else None)
        self._next_id += 1
        self.events.append(ev)
        self._open.append(ev)
        try:
            with TraceAnnotation(name):
                yield ev
        finally:
            ev.dur = self.clock() - ev.ts
            self._open.pop()

    def count(self, name: str, n: int = 1) -> None:
        self.counters[name] = self.counters.get(name, 0) + n

    def watch_compiles(self) -> None:
        """Count every backend compile in the process (JAX's
        ``backend_compile_duration`` event, persistent-cache loads
        included) under ``counters["compiles"]`` until the matching
        :meth:`unwatch_compiles`.  Calls nest: one per attachment."""
        global _listening
        if not _listening:
            from jax import monitoring
            monitoring.register_event_duration_secs_listener(_on_duration)
            _listening = True
        _compile_watchers[self] = _compile_watchers.get(self, 0) + 1

    def unwatch_compiles(self) -> None:
        n = _compile_watchers.get(self, 0) - 1
        if n > 0:
            _compile_watchers[self] = n
        else:
            _compile_watchers.pop(self, None)

    # ------------------------------------------------------------- queries
    def spans(self, prefix: str = "") -> List[TraceEvent]:
        """The :meth:`scope` spans whose name starts with ``prefix``, in
        the order they opened."""
        return [ev for ev in self.events
                if ev.id is not None and ev.name.startswith(prefix)]

    def actions_overlapping(self, t0: float, t1: float,
                            tenant: Optional[str] = None
                            ) -> List[TraceEvent]:
        """Controller actions whose [ts, ts+dur] intersects [t0, t1].
        ``tenant`` restricts to actions aimed at that tenant; pass None
        for all (a reconfigure pauses one tenant but its fabric /
        arbiter side effects are cluster-wide, so callers often want
        every overlapping action)."""
        out = []
        for ev in self.actions:
            if tenant is not None and ev.args.get("tenant") != tenant:
                continue
            if ev.ts <= t1 and ev.ts + ev.dur >= t0:
                out.append(ev)
        return out


def chrome_trace(events: List[TraceEvent]) -> Dict[str, Any]:
    """Render events as a Chrome/Perfetto ``trace_event`` JSON object.

    Tracks become processes and lanes become threads (named via ``"M"``
    metadata records); seconds become microseconds.  The result
    is ``json.dump``-able and loads directly in Perfetto's UI or
    ``chrome://tracing``.
    """
    pids: Dict[str, int] = {}
    tids: Dict[tuple, int] = {}
    out: List[Dict[str, Any]] = []
    for ev in events:
        track = ev.track or "default"
        lane = ev.lane or "-"
        if track not in pids:
            pids[track] = len(pids) + 1
            out.append({"name": "process_name", "ph": "M",
                        "pid": pids[track], "tid": 0,
                        "args": {"name": track}})
        key = (track, lane)
        if key not in tids:
            tids[key] = len(tids) + 1
            out.append({"name": "thread_name", "ph": "M",
                        "pid": pids[track], "tid": tids[key],
                        "args": {"name": lane}})
        rec: Dict[str, Any] = {
            "name": ev.name, "ph": ev.ph, "ts": ev.ts * 1e6,
            "pid": pids[track], "tid": tids[key], "cat": track,
            "args": ev.args}
        if ev.ph == "X":
            rec["dur"] = ev.dur * 1e6
        else:
            rec["s"] = "t"        # instant scope: thread
        out.append(rec)
    return {"traceEvents": out, "displayTimeUnit": "ms"}


def dump_chrome_trace(events: List[TraceEvent], path: str) -> None:
    with open(path, "w") as f:
        json.dump(chrome_trace(events), f)
