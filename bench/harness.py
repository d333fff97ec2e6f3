"""What both drivers share: the device, the compile counter, host spans,
the traced window and the result line."""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

import jax

from bench import spec, trace as trace_mod

OUT_DIR = spec.BENCH_DIR / "out"


def device_info() -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int | None:
    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


class CompileCounter:
    """Counts traces, compilations and compile-cache lookups while armed."""

    def __init__(self):
        self.armed = False
        self.events: list[str] = []
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _record(self, name: str):
        if self.armed and (name.startswith("/jax/core/compile")
                           or name.startswith("/jax/compilation_cache/"
                                              "compile_requests")):
            self.events.append(name)

    def _event(self, name, **_):
        self._record(name)

    def _duration(self, name, _secs, **_):
        self._record(name)


def span(name: str):
    """A host span in the profiler's trace (free when no trace runs)."""
    return jax.profiler.TraceAnnotation(name)


class Window:
    """The measured window: wall clock, compile counter, optional trace."""

    def __init__(self, seconds: float, traced: bool, tag: str):
        self.seconds = seconds
        self.traced = traced
        self.trace_dir = OUT_DIR / "traces" / tag
        self.counter = CompileCounter()
        self.t0 = self.t_end = None

    def __enter__(self):
        if self.traced:
            self.trace_dir.mkdir(parents=True, exist_ok=True)
            for old in self.trace_dir.rglob("*.xplane.pb"):
                old.unlink()
            jax.profiler.start_trace(str(self.trace_dir))
        self.counter.armed = True
        self.t0 = time.perf_counter()
        self.deadline = self.t0 + self.seconds
        return self

    def open(self) -> bool:
        return time.perf_counter() < self.deadline

    def __exit__(self, *exc):
        self.t_end = time.perf_counter()
        self.counter.armed = False
        if self.traced:
            jax.profiler.stop_trace()
        return False

    def reduced_trace(self) -> "trace_mod.Reduced":
        path = max(self.trace_dir.rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
        return trace_mod.reduce(trace_mod.load(path))


def check_lines(checks: dict) -> dict:
    """Print each compared number beside its limit, last on stderr."""
    out = {}
    for name, (value, limit) in checks.items():
        out[name] = {"value": value, "limit": limit}
        print(f"check {name}: {value!r} (limit {limit!r})", file=sys.stderr)
    return out


def emit(result: dict):
    print(json.dumps(result), flush=True)


def per_layer(cell: dict, ctx: dict) -> dict:
    """Read every per-layer metric that lists this cell."""
    out = {}
    for m in spec.benchmark()["per_layer"]:
        if cell["name"] not in m.get("workloads", [cell["name"]]):
            continue
        v = spec.metric_reader(m["name"])(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def write_json(name: str, obj) -> Path:
    OUT_DIR.mkdir(parents=True, exist_ok=True)
    p = OUT_DIR / name
    p.write_text(json.dumps(obj))
    return p
