"""Per-call records: where the host's time went inside one entry call.

An entry point of the program (``fednc.round``, ``serve.tick``) opens a
:class:`Record` with :func:`record`.  While it is open, every span that
closes adds its host nanoseconds to ``rec.spans`` (summed by name),
every :func:`count` — and every ``MetricsRegistry`` counter increment —
adds to ``rec.counts``, and :func:`sample` appends values to
``rec.samples``.  Closed records go to one process-wide ring of the
last :data:`RECORDS_MAX`.  The open record is per thread, so calls on
different threads never mix their phases.

Records are kept whether or not a ``Tracer`` is enabled: a record costs
a few clock reads and dict updates per span, so a run cannot (and need
not) tell the program that it is being measured.  Records nest: only
the outermost entry opens one, and the inner entry's spans land in it.

>>> with record("demo.call"):
...     count("demo.items", 3)
...     sample("demo.wait_s", [0.5, 0.25])
>>> rec = last_records("demo.call", 1)[0]
>>> rec.counts, rec.samples
({'demo.items': 3}, {'demo.wait_s': [0.5, 0.25]})
>>> last_records("demo.call", 10**6) is None       # fewer in the ring
True
"""
from __future__ import annotations

import threading
import time
from collections import deque
from typing import Iterable, Optional

#: records the ring keeps (the oldest fall out first)
RECORDS_MAX = 4096


class Record:
    """One entry call: clock seconds at open and close, span
    nanoseconds and counts summed by name, and samples by name."""

    __slots__ = ("name", "t0", "t1", "spans", "counts", "samples")

    def __init__(self, name: str):
        self.name = name
        self.t0 = time.perf_counter()
        self.t1 = self.t0
        self.spans: dict[str, int] = {}
        self.counts: dict[str, int] = {}
        self.samples: dict[str, list] = {}

    def span_ms(self, *names: str) -> float:
        """Summed host milliseconds of the spans called `names`."""
        return sum(self.spans.get(n, 0) for n in names) / 1e6


_ring: deque = deque(maxlen=RECORDS_MAX)
_local = threading.local()           # .rec: this thread's open record


def _open() -> Optional[Record]:
    return getattr(_local, "rec", None)


class _Entry:
    __slots__ = ("_name", "_rec")

    def __init__(self, name: str):
        self._name = name
        self._rec = None

    def __enter__(self) -> Record:
        rec = _open()
        if rec is None:
            rec = self._rec = _local.rec = Record(self._name)
        return rec

    def __exit__(self, exc_type, exc, tb) -> bool:
        rec = self._rec
        if rec is not None:
            rec.t1 = time.perf_counter()
            _local.rec = None
            _ring.append(rec)
        return False


def record(name: str) -> _Entry:
    """``with record("fednc.round"):`` — open a record unless one is
    already open (then the inner entry adds to the outer record)."""
    return _Entry(name)


def add_span(name: str, ns: int) -> None:
    """Add a closed span's host nanoseconds to the open record."""
    rec = _open()
    if rec is not None:
        rec.spans[name] = rec.spans.get(name, 0) + ns


def count(name: str, n: int = 1) -> None:
    """Add `n` to the open record's count `name` (no-op outside one)."""
    rec = _open()
    if rec is not None:
        rec.counts[name] = rec.counts.get(name, 0) + n


def sample(name: str, values: Iterable[float]) -> None:
    """Append `values` to the open record's samples `name`."""
    rec = _open()
    if rec is not None:
        rec.samples.setdefault(name, []).extend(values)


def records(name: Optional[str] = None) -> list[Record]:
    """The ring's records of `name` (all of them for None), oldest
    first."""
    return [r for r in _ring if name is None or r.name == name]


def last_records(name: str, n: int) -> Optional[list[Record]]:
    """The last `n` records of `name`, or None if the ring holds fewer
    (or `n` < 1)."""
    recs = records(name)
    if n < 1 or len(recs) < n:
        return None
    return recs[-n:]
