"""The benchmark harness: find a cell's files by name, run it once, report.

Layout (everything found by the name ``BENCHMARK.json`` gives it):

* ``configs/<config>.json`` — a deployment: the update tree, field, and
  the guarantees the comparison holds the run to.
* ``traffic/<traffic>.json`` — a traffic mix: its ``entry`` (the path
  path module that serves it) and the parameters the generator reads.
* ``paths/<entry>.py`` — one module per entry point of the program, with
  ``setup(ctx)``, ``window(state, seconds)`` and ``check(state, obs)``.
* ``metrics/<metric>.py`` — one reader per metric: ``read(run)`` returns
  the number, or None where the run has nothing to read.

A run: set-up (inputs from the seed, every shape the window uses warmed
up), the measured window, the device's peak memory, then the comparison
with the plain reference, and one JSON result line.
"""
from __future__ import annotations

import contextlib
import glob
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Callable, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class NoChip(RuntimeError):
    """JAX found no device of the kind the cell needs."""


def clock() -> float:
    return time.perf_counter()


# ---------------------------------------------------------------------------
# finding things by name
# ---------------------------------------------------------------------------

def _load_module(path: Path, kind: str):
    """Import a benchmark file by path, once per process."""
    name = f"chipbench_{kind}_{abs(hash(str(path))):x}"
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Layout:
    """The benchmark's files under `base`, and ``BENCHMARK.json`` at `root`."""

    base: Path = HERE
    root: Path = ROOT

    def benchmark(self) -> dict:
        return json.loads((self.root / "BENCHMARK.json").read_text())

    def _stems(self, sub: str, suffix: str) -> list[str]:
        return sorted(p.name[: -len(suffix)]
                      for p in (self.base / sub).glob("*" + suffix)
                      if not p.name.startswith("_"))

    def discover(self) -> dict[str, list[str]]:
        return {"configs": self._stems("configs", ".json"),
                "traffic": self._stems("traffic", ".json"),
                "paths": self._stems("paths", ".py"),
                "metrics": self._stems("metrics", ".py")}

    def config(self, name: str) -> dict:
        return json.loads((self.base / "configs" / f"{name}.json").read_text())

    def traffic(self, name: str) -> dict:
        return json.loads((self.base / "traffic" / f"{name}.json").read_text())

    def path(self, entry: str):
        return _load_module(self.base / "paths" / f"{entry}.py", "path")

    def metric(self, name: str):
        return _load_module(self.base / "metrics" / f"{name}.py", "metric")

    def peaks(self) -> dict:
        return json.loads((self.base / "peaks.json").read_text())["devices"]


def cell_entry(bench: dict, cell: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == cell:
            return w
    raise KeyError(f"no workload named {cell!r} in BENCHMARK.json")


def cell_metrics(bench: dict, cell: str, trace: bool) -> list[dict]:
    """The metrics a run of `cell` reports: its end-to-end metrics with
    ``--trace 0``, its per-layer metrics with ``--trace 1``."""
    group = bench["per_layer" if trace else "end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


# ---------------------------------------------------------------------------
# run-time helpers handed to the path modules
# ---------------------------------------------------------------------------

class CompileCounter:
    """Counts programs compiled or loaded from the persistent cache
    (``count``, ``seconds``) and the loads among them (``hits``)."""

    def __init__(self):
        self.count = self.hits = 0
        self.seconds = 0.0
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.count += 1
            self.seconds += duration

    def _on_event(self, event, **_):
        if event == CACHE_HIT_EVENT:
            self.hits += 1


@dataclass
class Ctx:
    """What a path module's ``setup`` gets."""

    config: dict
    traffic: dict
    seed: int
    devices: list
    seconds: float = 0.0                  # the window the run will measure
    control: bool = False
    tracing: bool = False
    rate: Optional[float] = None          # serve cells: offered-load override
    drain_s: float = 60.0                 # wait for late answers

    def annotate(self, name: str):
        """A host span in the profiler's trace (a no-op when not tracing)."""
        if not self.tracing:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)


@dataclass
class Run:
    """What a metric reader gets."""

    cell: str
    config: dict
    traffic: dict
    obs: dict
    setup_s: float
    peak_bytes: Optional[int]
    peaks: dict
    trace: Any = None                      # profile.Summary with --trace 1


def check_devices(chips: int, peaks: dict) -> list:
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {dev.platform!r})")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devices)}")
    if dev.device_kind not in peaks:
        raise NoChip(f"device kind {dev.device_kind!r} is not in peaks.json")
    return devices[:chips]


def _peak_bytes(devices) -> Optional[int]:
    vals = [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devices]
    vals = [v for v in vals if v is not None]
    return int(max(vals)) if vals else None


def _profile_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


def run_cell(cell: str, seed: int, seconds: float, trace: bool, *,
             layout: Layout = None, t0: float = None, devices=None,
             control: bool = False, config: dict = None,
             traffic: dict = None, rate: float = None,
             drain_s: float = 60.0, log: Callable = None) -> dict:
    """Run one cell once; return the result line as a dict.

    `devices` skips the look for a chip (tests pass the CPU's); `config`
    and `traffic` replace the files the cell names (tests shrink them).
    """
    from chipbench import profile

    layout = layout or Layout()
    t0 = clock() if t0 is None else t0
    log = log or (lambda rec: print(json.dumps(rec), flush=True))
    bench = layout.benchmark()
    entry = cell_entry(bench, cell)
    config = config or layout.config(entry["config"])
    traffic = traffic or layout.traffic(entry["traffic"])
    peaks_all = layout.peaks()
    if devices is None:
        devices = check_devices(int(entry["chips"]), peaks_all)
    kind = devices[0].device_kind
    peaks = peaks_all.get(kind, {})
    path = layout.path(traffic["entry"])
    metrics = cell_metrics(bench, cell, trace)
    readers = {m["name"]: layout.metric(m["name"]) for m in metrics}

    counter = CompileCounter()
    ctx = Ctx(config, traffic, int(seed), list(devices), float(seconds),
              control=control,
              tracing=trace, rate=rate, drain_s=drain_s)
    state = path.setup(ctx)
    setup_s = clock() - t0
    setup = {"setup_s": setup_s, "setup_programs": counter.count,
             "setup_cache_hits": counter.hits,
             "setup_compile_s": counter.seconds}

    tmp = None
    if trace:
        import jax
        tmp = tempfile.mkdtemp(prefix="chipbench-trace-")
        jax.profiler.start_trace(tmp, profiler_options=_profile_options())
    c0, s0, h0 = counter.count, counter.seconds, counter.hits
    obs = path.window(state, float(seconds))
    window = {"window_programs": counter.count - c0,
              "window_cache_misses": (counter.count - c0)
              - (counter.hits - h0),
              "window_compile_s": counter.seconds - s0}
    summary = None
    if trace:
        import jax
        jax.profiler.stop_trace()
        pb = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)
        tr = profile.read(pb[0])
        matches = sorted({getattr(r, "MATCH", None) for r in readers.values()}
                         - {None})
        summary = profile.summarize(tr, path.TIMED, path.PHASES, matches)
        shutil.rmtree(tmp, ignore_errors=True)
    peak = _peak_bytes(devices)
    log({**setup, **window, **path.describe(obs)})

    checks, attempted, failed = path.check(state, obs)
    run = Run(cell, config, traffic, obs, setup_s, peak, peaks, summary)
    values = {}
    for m in metrics:
        v = readers[m["name"]].read(run)
        if v is not None:
            values[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": peak or 0}
    result = {"correct": all(c["ok"] for c in checks),
              "attempted": int(attempted), "failed": int(failed),
              "metrics": values, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = {"device_ops": summary.device_ops,
                               "idle_gaps": summary.idle_gaps}
    result["checks"] = [{k: c[k] for k in ("name", "value", "limit", "op")}
                        for c in checks]
    return result


def check(name: str, value, limit, op: str) -> dict:
    """One compared number beside its limit; ``op`` is ``<=`` or ``>=``."""
    ok = value <= limit if op == "<=" else value >= limit
    return {"name": name, "value": value, "limit": limit, "op": op,
            "ok": bool(ok)}


def print_result(result: dict) -> None:
    """Compared numbers as the last lines on stderr; the result line last
    on stdout."""
    for c in result["checks"]:
        print(f"check {c['name']} = {c['value']} (limit {c['op']} "
              f"{c['limit']})", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
