"""From a profiler trace (``.xplane.pb``) to device busy time, kernel time,
top operations and idle gaps.

A TPU trace has one plane per chip (``/device:TPU:<n>``) with an
``XLA Ops`` line (one event per HLO operation that ran) and an ``XLA
Modules`` line (one event per program executed).  On the CPU backend the
same operations are host-thread events that carry ``hlo_op`` and
``hlo_module`` stats; they stand in for a device so the reduction can be
tested without a chip.  The benchmark's own host spans are
``jax.profiler.TraceAnnotation`` events on the host plane, on the same
clock.

Everything is restricted to a *window*: the union of the host spans the
benchmark marked as timed.  Busy time is the length of the union of the
operation intervals inside the window, averaged over devices; a kernel's
time is the length of the union of its operations' intervals inside the
window.  Intervals are in nanoseconds.
"""
from __future__ import annotations

import bisect
import re
from dataclasses import dataclass, field
from typing import Iterable, Optional

Interval = tuple[float, float]


def union(intervals: Iterable[Interval]) -> list[Interval]:
    """Merge overlapping or touching intervals; drops empty ones."""
    out: list[list[float]] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def intersect(a: list[Interval], b: list[Interval]) -> list[Interval]:
    """Intersection of two merged, sorted interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s = max(a[i][0], b[j][0])
        e = min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def complement(within: list[Interval], busy: list[Interval]
               ) -> list[Interval]:
    """The parts of `within` that `busy` does not cover (both merged)."""
    out = []
    for s, e in within:
        t = s
        for bs, be in intersect([(s, e)], busy):
            if bs > t:
                out.append((t, bs))
            t = be
        if e > t:
            out.append((t, e))
    return out


_OP_NAME = re.compile(r"^%?([^\s=]+)")
_HASH = re.compile(r"\(\d+\)$")
_SUFFIX = re.compile(r"(\.\d+)+$")


def op_short_name(text: str) -> str:
    """``%gf_matmul_packed.1 = s32[..] custom-call(..)`` -> ``gf_matmul_packed.1``."""
    m = _OP_NAME.match(text.strip())
    return m.group(1) if m else text


def module_short_name(text: str) -> str:
    """``jit_gf_matmul_pallas_packed(1296..)`` -> ``jit_gf_matmul_pallas_packed``."""
    return _HASH.sub("", text.strip())


@dataclass(frozen=True)
class Op:
    device: str
    module: str
    name: str
    start: float
    end: float


@dataclass
class Trace:
    """The parts of one trace the benchmark reads."""

    ops: list[Op]
    spans: list[tuple[str, float, float]]      # host annotations
    devices: list[str] = field(default_factory=list)

    def window(self, names: Iterable[str]) -> list[Interval]:
        names = set(names)
        return union((s, e) for n, s, e in self.spans if n in names)

    def busy(self, window: list[Interval], device: Optional[str] = None,
             match: Optional[str] = None) -> list[Interval]:
        """Merged op intervals inside `window`, of one device or all, of
        the ops whose op or module name contains `match` (or all)."""
        ivs = union((o.start, o.end) for o in self.ops
                    if (device is None or o.device == device)
                    and (match is None or match in o.name
                         or match in o.module))
        return intersect(ivs, window)


def _stats(ev) -> dict:
    try:
        return {k: v for k, v in ev.stats}
    except (TypeError, ValueError):
        return {}


def _device_ops(plane) -> list[Op]:
    lines = {l.name: l for l in plane.lines}
    mods = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                  for e in lines["XLA Modules"].events) \
        if "XLA Modules" in lines else []
    starts = [m[0] for m in mods]
    ops = []
    for e in lines["XLA Ops"].events:
        s, d = e.start_ns, e.duration_ns
        module = ""
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and mods[i][1] >= s:
            module = module_short_name(mods[i][2])
        ops.append(Op(plane.name, module, op_short_name(e.name), s, s + d))
    return ops


def read(path: str, *, span_prefix: str = "bench.") -> Trace:
    """Read one ``.xplane.pb``.  Host spans kept are those whose name
    starts with `span_prefix` (the benchmark's own annotations)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops: list[Op] = []
    spans: list[tuple[str, float, float]] = []
    devices: list[str] = []
    cpu_ops: list[Op] = []
    for plane in data.planes:
        line_names = {l.name for l in plane.lines}
        if plane.name.startswith("/device:") and "XLA Ops" in line_names:
            devices.append(plane.name)
            ops.extend(_device_ops(plane))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(span_prefix):
                        spans.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns))
                    elif e.duration_ns > 0 and not line.name == "python":
                        st = _stats(e)
                        if "hlo_op" in st:
                            cpu_ops.append(Op(
                                "/host:CPU", module_short_name(
                                    str(st.get("hlo_module", ""))),
                                op_short_name(e.name), e.start_ns,
                                e.start_ns + e.duration_ns))
    if not devices and cpu_ops:
        devices, ops = ["/host:CPU"], cpu_ops
    return Trace(ops, spans, devices)


@dataclass
class Summary:
    """What the metrics read from a trace: seconds, and names."""

    window_s: float
    busy_s: float                    # averaged over the devices
    matched: dict                    # substring -> seconds (device mean)
    device_ops: list                 # [[name, seconds], ...] top 10
    idle_gaps: list                  # [[host phase, seconds], ...] top 10
    n_ops: int

    def matched_s(self, substring: str) -> Optional[float]:
        return self.matched.get(substring)


def summarize(trace: Trace, timed: Iterable[str], phases: Iterable[str],
              match: Iterable[str] = (), top: int = 10) -> Summary:
    """Reduce a trace to a :class:`Summary` over the `timed` spans.

    `phases` are the host span names an idle gap may be attributed to
    (the one overlapping it most); `match` the substrings whose kernel
    time is wanted.
    """
    win = trace.window(timed)
    win_s = length(win) * 1e-9
    devs = trace.devices or ["?"]
    busy = {d: trace.busy(win, d) for d in devs}
    busy_s = sum(length(b) for b in busy.values()) / len(devs) * 1e-9
    matched = {m: sum(length(trace.busy(win, d, m)) for d in devs)
               / len(devs) * 1e-9 for m in match}
    # top operations by device time inside the window
    by_name: dict[str, float] = {}
    for o in trace.ops:
        t = length(intersect([(o.start, o.end)], win))
        if t > 0:
            key = f"{o.module}:{_SUFFIX.sub('', o.name)}" if o.module \
                else _SUFFIX.sub("", o.name)
            by_name[key] = by_name.get(key, 0.0) + t
    device_ops = [[k, v * 1e-9 / len(devs)] for k, v in
                  sorted(by_name.items(), key=lambda kv: -kv[1])[:top]]
    # idle gaps of the first device, named by the host phase they fell in
    phase_spans = [(n, s, e) for n, s, e in trace.spans if n in set(phases)]
    gaps = sorted(complement(win, busy[devs[0]]), key=lambda g: g[0] - g[1])
    named = []
    for s, e in gaps[:top]:
        best, best_t = "none", 0.0
        for n, ps, pe in phase_spans:
            t = min(e, pe) - max(s, ps)
            if t > best_t:
                best, best_t = n, t
        named.append([best.removeprefix("bench."), (e - s) * 1e-9])
    named.sort(key=lambda g: -g[1])
    return Summary(win_s, busy_s, matched, device_ops, named[:top],
                   len(trace.ops))
