"""The tracing core: Chrome trace-event JSON, one timing idiom.

Every wall-clock number this repo publishes used to come from an
ad-hoc ``time.perf_counter()`` pair; this module replaces that with
two primitives sharing one clock:

* :meth:`Tracer.span` — a span marks a *host phase*: the time the
  host spends in a block of the program.  It never blocks on device
  work, so a round dispatches the same programs in the same order with
  the same host syncs whether tracing is on or off; device time comes
  from the profiler's device trace.  Every span, enabled tracer or
  not, enters ``jax.profiler.TraceAnnotation(<name>)`` (which puts it
  on the profiler's host plane, on the device trace's clock, and costs
  next to nothing when no profile is being taken) and adds its host
  nanoseconds to the open per-call record (:mod:`repro.obs.ring`).
  An *enabled* :class:`Tracer` also keeps it, with instants (``"i"``)
  and counter samples (``"C"``), as Chrome trace events that save as
  trace-event JSON (open it in Perfetto / ``chrome://tracing``).  The
  module-level active tracer defaults to :data:`NULL_TRACER`, which
  keeps no events.
* :func:`timed` — an always-on stopwatch for the wall numbers call
  sites need regardless of tracing (``RoundLog.wall_s``, grid cell
  walls, benchmark loops).  ``sw.fence(out)`` blocks on device work
  before the clock stops (:func:`device_sync` ->
  ``jax.block_until_ready``), so the wall covers the device work the
  block dispatched.  When the active tracer is enabled the same
  measurement also lands in the trace as a span.

Event timestamps are epoch-anchored microseconds
(``time_ns`` offset measured once per process against
``perf_counter_ns``), so traces recorded by different processes —
e.g. ``run_grid(jobs=N)`` spawn workers — merge onto one timeline,
each process in its own pid lane.
"""
from __future__ import annotations

import json
import os
import pathlib
import threading
import time
from typing import Optional

from repro.obs import ring

#: schema tag recorded in the trace document's ``otherData``
TRACE_SCHEMA = "fednc-trace-v1"

# perf_counter gives the best-resolution monotonic durations; the
# offset anchors its arbitrary origin to the epoch once per process so
# per-process lanes share a timeline when merged
_EPOCH_OFFSET_NS = time.time_ns() - time.perf_counter_ns()


def clock() -> float:
    """Monotonic seconds — THE clock every obs measurement uses."""
    return time.perf_counter()


def device_sync(x):
    """Fence: block until every device computation in `x` finished.

    No-op for None and for values jax does not recognize (plain
    floats, numpy arrays), so call sites can pass whatever the block
    produced without caring about its type.  Returns `x`."""
    if x is None:
        return x
    try:
        import jax
    except ImportError:                                # pragma: no cover
        return x
    try:
        jax.block_until_ready(x)
    except (TypeError, ValueError):                    # non-pytree values
        pass
    return x


class Span:
    """One host phase: ``with obs.span("engine.select"):``.

    Reads the clock at entry and exit and never blocks on device work.
    ``sp.dur_s`` holds the duration after exit; ``sp.set(**args)``
    attaches args to the Chrome event of an enabled tracer."""

    __slots__ = ("_tracer", "name", "cat", "args", "_t0", "_ann",
                 "dur_s")

    def __init__(self, tracer: "Optional[Tracer]", name: str, cat: str,
                 args: dict):
        self._tracer = tracer
        self.name = name
        self.cat = cat
        self.args = args
        self.dur_s = 0.0

    def set(self, **args) -> "Span":
        """Attach/override span args from inside the block."""
        self.args.update(args)
        return self

    def __enter__(self) -> "Span":
        self._ann = _annotation(self.name)
        self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        t1 = time.perf_counter_ns()
        self._ann.__exit__(exc_type, exc, tb)
        self.dur_s = (t1 - self._t0) / 1e9
        ring.add_span(self.name, t1 - self._t0)
        if self._tracer is not None:
            self._tracer._complete(self.name, self.cat, self._t0, t1,
                                   self.args)
        return False


_TraceAnnotation = None


def _annotation(name: str):
    """``jax.profiler.TraceAnnotation(name)`` (jax imported on first
    use, so importing repro.obs does not import jax)."""
    global _TraceAnnotation
    if _TraceAnnotation is None:
        from jax.profiler import TraceAnnotation as _TraceAnnotation
    return _TraceAnnotation(name)


class NullTracer:
    """Disabled tracer: spans still mark host phases (profiler
    annotation, per-call records) but no Chrome event is kept."""

    enabled = False
    events: tuple = ()

    def span(self, name: str, cat: str = "", **args) -> Span:
        return Span(None, name, cat, args)

    def instant(self, name: str, cat: str = "", **args) -> None:
        pass

    def counter(self, name: str, value, cat: str = "") -> None:
        pass

    def extend(self, events) -> None:
        pass


NULL_TRACER = NullTracer()


class Tracer:
    """An enabled tracer accumulating Chrome trace events in memory.

    >>> tr = Tracer(process_name="doctest")
    >>> with tr.span("work", cat="demo", items=3):
    ...     pass
    >>> tr.instant("mark", cat="demo")
    >>> tr.counter("depth", 4)
    >>> [e["ph"] for e in tr.events if e["ph"] != "M"]
    ['X', 'i', 'C']
    """

    enabled = True

    def __init__(self, process_name: Optional[str] = None):
        self.events: list[dict] = []
        self.pid = os.getpid()
        self._tids: dict[int, int] = {}
        self._lock = threading.Lock()
        if process_name:
            self.events.append({
                "name": "process_name", "ph": "M", "pid": self.pid,
                "tid": 0, "args": {"name": str(process_name)},
            })

    # -- internals --------------------------------------------------------

    def _tid(self) -> int:
        ident = threading.get_ident()
        tid = self._tids.get(ident)
        if tid is None:
            with self._lock:
                tid = self._tids.setdefault(ident, len(self._tids))
        return tid

    @staticmethod
    def _ts(t_ns: int) -> float:
        return (t_ns + _EPOCH_OFFSET_NS) / 1e3       # epoch microseconds

    def _complete(self, name: str, cat: str, t0_ns: int, t1_ns: int,
                  args: dict) -> None:
        ev = {"name": name, "cat": cat, "ph": "X",
              "ts": self._ts(t0_ns), "dur": (t1_ns - t0_ns) / 1e3,
              "pid": self.pid, "tid": self._tid()}
        if args:
            ev["args"] = args
        self.events.append(ev)

    # -- the emitting API -------------------------------------------------

    def span(self, name: str, cat: str = "", **args) -> Span:
        """A duration ("X") event as a context manager."""
        return Span(self, name, cat, args)

    def instant(self, name: str, cat: str = "", **args) -> None:
        """A point-in-time ("i") event (arrivals, completions, ...)."""
        ev = {"name": name, "cat": cat, "ph": "i", "s": "t",
              "ts": self._ts(time.perf_counter_ns()),
              "pid": self.pid, "tid": self._tid()}
        if args:
            ev["args"] = args
        self.events.append(ev)

    def counter(self, name: str, value, cat: str = "") -> None:
        """A counter-track ("C") sample — Perfetto renders these as
        per-tick counter lanes (queue depth, slot occupancy, ...)."""
        self.events.append({
            "name": name, "cat": cat, "ph": "C",
            "ts": self._ts(time.perf_counter_ns()),
            "pid": self.pid, "tid": self._tid(),
            "args": {name: float(value)},
        })

    def extend(self, events) -> None:
        """Merge events recorded elsewhere (e.g. a worker process —
        they keep their own pid, so they land in their own lane)."""
        self.events.extend(events)

    # -- the document -----------------------------------------------------

    def to_document(self) -> dict:
        return events_document(self.events)

    def save(self, path) -> pathlib.Path:
        return save_events(self.events, path)


def events_document(events) -> dict:
    """Wrap an event list as a Chrome trace-event JSON document."""
    return {
        "traceEvents": list(events),
        "displayTimeUnit": "ms",
        "otherData": {"schema": TRACE_SCHEMA},
    }


def save_events(events, path) -> pathlib.Path:
    path = pathlib.Path(path)
    path.write_text(json.dumps(events_document(events)))
    return path


# -- the active tracer ------------------------------------------------------

_active: "Tracer | NullTracer" = NULL_TRACER


def get_tracer():
    """The process-wide active tracer (NULL_TRACER unless enabled)."""
    return _active


def span(name: str, cat: str = "", **args) -> Span:
    """A host-phase span on the active tracer (see :class:`Span`)."""
    return _active.span(name, cat, **args)


def set_tracer(tracer):
    """Install `tracer` as the active tracer; returns it.

    ``set_tracer(NULL_TRACER)`` disables tracing again."""
    global _active
    _active = tracer
    return tracer


class Stopwatch:
    """Always-on timing: measures even when tracing is disabled, and
    additionally emits a span into `tracer` when it is enabled."""

    __slots__ = ("name", "cat", "args", "_tracer", "_pending", "_t0",
                 "dur_s")

    def __init__(self, name: str, cat: str, tracer, args: dict):
        self.name = name
        self.cat = cat
        self.args = args
        self._tracer = tracer
        self._pending = None
        self.dur_s = 0.0

    def fence(self, x):
        """Block on `x` (device work) before the clock stops."""
        self._pending = x
        return x

    def set(self, **args) -> "Stopwatch":
        self.args.update(args)
        return self

    def __enter__(self) -> "Stopwatch":
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if self._pending is not None:
            device_sync(self._pending)
            self._pending = None
        t1 = time.perf_counter_ns()
        self.dur_s = (t1 - self._t0) / 1e9
        tr = self._tracer
        if tr is not None and tr.enabled:
            tr._complete(self.name, self.cat, self._t0, t1, self.args)
        return False


_USE_ACTIVE = object()


def timed(name: str, cat: str = "", tracer=_USE_ACTIVE,
          **args) -> Stopwatch:
    """The repo-wide stopwatch idiom (replaces raw ``perf_counter``).

    >>> with timed("demo.sleep", cat="demo") as sw:
    ...     _ = sum(range(10))
    >>> sw.dur_s >= 0.0
    True
    """
    if tracer is _USE_ACTIVE:
        tracer = get_tracer()
    return Stopwatch(name, cat, tracer, args)
