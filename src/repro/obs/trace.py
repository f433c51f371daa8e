"""Tracer: nested host spans on the profiler's clock.

The flight-recorder half of `repro.obs` (the other half is the metrics
registry, `obs.metrics`).  A `Tracer` records two event kinds:

  - **spans** — `with tracer.span("engine.upload"):` records host wall time
    between enter and exit.  Spans nest (a per-thread stack tracks the open
    parent), carry a process-monotonic id and optional `key=value`
    attributes.  Each span also enters a `jax.profiler.TraceAnnotation`
    named `"repro." + name`, so while a `jax.profiler` trace is being taken
    the span sits on the trace's host line, on the same clock as the device
    ops it launched.  A span never waits for the device: device time is
    read from the trace, where `jax.named_scope` names the fused
    program's phases (`engine.fused`).
  - **instant events** — `tracer.event("p2p.autotune", {...})` records a
    point-in-time marker (autotune decisions, cache events, probes).

`summary()` aggregates span wall time by name for `FMMSession.report()`.

Disabled mode lives one layer up: `repro.obs.span()` returns the shared
`NULL_SPAN` singleton when no tracer is installed — zero allocations, no
clock reads, no annotation — which the overhead test pins
(`tests/test_obs.py`).  The classes here therefore never check an enabled
flag themselves.
"""
from __future__ import annotations

import itertools
import threading
import time

from jax.profiler import TraceAnnotation

__all__ = ["Span", "NullSpan", "NULL_SPAN", "Tracer"]


class NullSpan:
    """The do-nothing span served while tracing is disabled.  A process-wide
    singleton (`NULL_SPAN`): entering, exiting and annotating all return
    immediately without allocating, so instrumented hot paths cost a dict
    lookup and an `is None` check when the recorder is off."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return False

    def set(self, attrs=None):
        return self


NULL_SPAN = NullSpan()


class Span:
    """One recorded interval.  Times are `time.perf_counter_ns` ticks;
    `sid`/`parent` are the tracer's monotonic span ids (parent -1 = top
    level).  While open, the span is also the profiler annotation
    `"repro." + name`."""

    __slots__ = ("tracer", "name", "attrs", "sid", "parent", "tid",
                 "t0_ns", "t1_ns", "_note")

    def __init__(self, tracer, name, attrs, sid, parent, tid):
        self.tracer = tracer
        self.name = name
        self.attrs = attrs
        self.sid = sid
        self.parent = parent
        self.tid = tid
        self.t0_ns = -1
        self.t1_ns = -1
        self._note = None

    def set(self, attrs=None):
        """Merge `attrs` into the span's attributes (post-hoc annotation:
        results only known at the end of the measured region)."""
        if attrs:
            if self.attrs is None:
                self.attrs = dict(attrs)
            else:
                self.attrs.update(attrs)
        return self

    def __enter__(self):
        self.tracer._push(self)
        self._note = TraceAnnotation("repro." + self.name)
        self._note.__enter__()
        self.t0_ns = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb):
        self.t1_ns = time.perf_counter_ns()
        self._note.__exit__(exc_type, exc, tb)
        self._note = None
        self.tracer._pop(self)
        return False

    @property
    def dur_s(self) -> float:
        return (self.t1_ns - self.t0_ns) / 1e9


class Tracer:
    """Span + instant-event recorder.

    Parameters
    ----------
    max_events : ring bound on retained finished events; the oldest half is
        dropped when exceeded (a flight recorder must never OOM the flight).
    """

    def __init__(self, *, max_events: int = 100_000):
        self.max_events = int(max_events)
        self.events: list = []          # finished Spans + instant dicts
        self.dropped = 0
        self._ids = itertools.count()
        self._tls = threading.local()
        self._lock = threading.Lock()

    # ------------------------------------------------------------- record --
    def _stack(self):
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def span(self, name: str, attrs=None) -> Span:
        st = self._stack()
        parent = st[-1].sid if st else -1
        return Span(self, name, dict(attrs) if attrs else None,
                    next(self._ids), parent, threading.get_ident())

    def _push(self, span: Span) -> None:
        self._stack().append(span)

    def _pop(self, span: Span) -> None:
        st = self._stack()
        if st and st[-1] is span:
            st.pop()
        else:                            # tolerate misnested exits
            try:
                st.remove(span)
            except ValueError:
                pass
        self._record(span)

    def event(self, name: str, attrs=None) -> None:
        """Record an instant event at the current time."""
        st = self._stack()
        self._record({"name": name,
                      "attrs": dict(attrs) if attrs else None,
                      "sid": next(self._ids),
                      "parent": st[-1].sid if st else -1,
                      "tid": threading.get_ident(),
                      "t_ns": time.perf_counter_ns()})

    def _record(self, ev) -> None:
        with self._lock:
            self.events.append(ev)
            if len(self.events) > self.max_events:
                drop = len(self.events) // 2
                del self.events[:drop]
                self.dropped += drop

    # --------------------------------------------------------------- read --
    def clear(self) -> None:
        with self._lock:
            self.events.clear()
            self.dropped = 0

    def spans(self, name: str | None = None) -> list:
        """Finished spans, oldest first, optionally filtered by name."""
        with self._lock:
            evs = list(self.events)
        return [e for e in evs if isinstance(e, Span)
                and (name is None or e.name == name)]

    def summary(self) -> dict:
        """Aggregate wall time by span name:
        {name: {count, total_s, mean_s, max_s}} — the `timings` block of
        `FMMSession.report()`."""
        agg: dict = {}
        for s in self.spans():
            a = agg.setdefault(s.name, {"count": 0, "total_s": 0.0,
                                        "max_s": 0.0})
            d = s.dur_s
            a["count"] += 1
            a["total_s"] += d
            a["max_s"] = max(a["max_s"], d)
        for a in agg.values():
            a["mean_s"] = a["total_s"] / a["count"]
        return agg
