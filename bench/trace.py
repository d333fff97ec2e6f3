"""Reduce a profiler trace to the numbers the per-layer metrics read.

``load`` reads an ``.xplane.pb`` with ``jax.profiler.ProfileData`` into
plain arrays: per chip, the intervals of the "XLA Ops" line (what ran on
the device) and of the "XLA Modules" line (one event per program
execution); on the host, the harness's own spans (``bench.*`` and the
names in ``HOST_SPANS``). ``reduce`` turns that into busy and idle time
inside the span ``bench.window``, the device ops that took most time, the
idle time by what the host was doing, and the module executions.
"""

from __future__ import annotations

import dataclasses
import re

import numpy as np

WINDOW = "bench.window"
# the host spans bench/drivers/ record; an idle gap is charged to the
# innermost one that covers its midpoint, else to "other"
HOST_SPANS = ("next_batch", "dispatch", "wait", "prefill", "decode",
              "token_fetch")
_DEVICE = re.compile(r"^/device:TPU:\d+$")


@dataclasses.dataclass
class Chip:
    ops: np.ndarray          # (n, 2) float64 start, end in ns
    op_names: list[str]
    modules: np.ndarray      # (m, 2) start, end
    module_names: list[str]


@dataclasses.dataclass
class Trace:
    chips: list[Chip]
    spans: list[tuple[str, float, float]]   # host spans: name, start, end


@dataclasses.dataclass
class Reduced:
    window: tuple[float, float]
    window_s: float
    busy_s: float                       # mean over chips
    device_ops: list[tuple[str, float]]  # top 10 by summed seconds
    idle_gaps: list[tuple[str, float]]   # idle seconds by host span
    modules: list[tuple[str, float, float]]   # chip 0: name, start, end
    spans: list[tuple[str, float, float]]
    busy: list[np.ndarray]              # per chip merged busy intervals


def _intervals(line):
    names, iv = [], []
    for e in line.events:
        names.append(e.name)
        iv.append((e.start_ns, e.start_ns + e.duration_ns))
    return np.asarray(iv, np.float64).reshape(-1, 2), names


def load(path) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    chips, spans = [], []
    keep = set(HOST_SPANS) | {WINDOW}
    for plane in pd.planes:
        if _DEVICE.match(plane.name):
            lines = {l.name: l for l in plane.lines}
            if "XLA Ops" not in lines:
                continue
            ops, op_names = _intervals(lines["XLA Ops"])
            if "XLA Modules" in lines:
                mods, mod_names = _intervals(lines["XLA Modules"])
            else:
                mods, mod_names = np.zeros((0, 2)), []
            chips.append(Chip(ops, op_names, mods, mod_names))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name in keep:
                        spans.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns))
    return Trace(chips, spans)


def merge(iv: np.ndarray) -> np.ndarray:
    """Union of intervals, as sorted disjoint intervals."""
    if len(iv) == 0:
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    reach = np.maximum.accumulate(iv[:, 1])
    new = np.concatenate([[True], iv[1:, 0] > reach[:-1]])
    starts = iv[new, 0]
    ends = reach[np.concatenate([np.flatnonzero(new)[1:] - 1, [len(iv) - 1]])]
    return np.stack([starts, ends], axis=1)


def clip(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    iv = np.clip(iv, lo, hi)
    return iv[iv[:, 1] > iv[:, 0]]


def covered(busy: np.ndarray, lo: float, hi: float) -> float:
    """Nanoseconds of [lo, hi] that the merged intervals cover."""
    return float(np.diff(clip(busy, lo, hi), axis=1).sum())


def reduce(tr: Trace, top: int = 10) -> Reduced:
    wins = [s for s in tr.spans if s[0] == WINDOW]
    if not wins:
        raise ValueError(f"no {WINDOW!r} span in the trace")
    if not tr.chips:
        raise ValueError("no TPU device plane with an 'XLA Ops' line")
    _, w0, w1 = wins[0]
    busy = [merge(clip(c.ops, w0, w1)) for c in tr.chips]
    busy_ns = np.mean([float(np.diff(b, axis=1).sum()) for b in busy])

    per_op: dict[str, float] = {}
    c0 = tr.chips[0]
    ops = np.clip(c0.ops, w0, w1)
    for name, t in zip(c0.op_names, self_times(ops)):
        per_op[name] = per_op.get(name, 0.0) + t * 1e-9
    device_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]

    # cut the idle time at every host span edge, and charge each piece to
    # the innermost span that covers it
    gaps = _gaps(busy[0], w0, w1)
    edges = np.array([t for _, a, b in tr.spans for t in (a, b)
                      if w0 < t < w1])
    cuts = np.unique(np.concatenate([gaps.ravel(), edges]))
    mids = 0.5 * (cuts[1:] + cuts[:-1])
    k = np.searchsorted(gaps[:, 0], mids, side="right") - 1
    idle_mask = (k >= 0) & (mids < gaps[np.maximum(k, 0), 1])
    idle: dict[str, float] = {}
    pieces = np.diff(cuts)[idle_mask]
    for label, d in zip(label_points(tr.spans, mids[idle_mask]), pieces):
        idle[label] = idle.get(label, 0.0) + d * 1e-9
    idle_gaps = sorted(idle.items(), key=lambda kv: -kv[1])[:top]

    # the trace holds only the window, so every execution in it counts,
    # even where the device's clock puts it a little outside the span
    mods = [(n, s, e) for n, (s, e) in zip(c0.module_names, c0.modules)]
    return Reduced((w0, w1), (w1 - w0) * 1e-9, busy_ns * 1e-9, device_ops,
                   idle_gaps, mods, tr.spans, busy)


def self_times(iv: np.ndarray) -> np.ndarray:
    """Duration of each interval less that of the intervals nested in it
    (a loop op holds its body's ops on the same line)."""
    dur = iv[:, 1] - iv[:, 0]
    own = dur.copy()
    stack: list[int] = []
    for i in np.lexsort((-iv[:, 1], iv[:, 0])):
        while stack and iv[stack[-1], 1] <= iv[i, 0]:
            stack.pop()
        if stack:
            own[stack[-1]] -= dur[i]
        stack.append(i)
    return own


def _gaps(busy: np.ndarray, lo: float, hi: float) -> np.ndarray:
    edges = np.concatenate([[lo], busy.ravel(), [hi]]).reshape(-1, 2)
    return edges[edges[:, 1] > edges[:, 0]]


def label_points(spans, points: np.ndarray) -> list[str]:
    """The innermost host span (other than the window) covering each
    point, or "other"."""
    best = np.full(len(points), np.inf)
    label = np.full(len(points), "other", dtype=object)
    for name in {n for n, _, _ in spans if n != WINDOW}:
        iv = np.asarray(sorted((a, b) for n, a, b in spans if n == name))
        i = np.searchsorted(iv[:, 0], points, side="right") - 1
        ok = i >= 0
        i = np.maximum(i, 0)
        dur = iv[i, 1] - iv[i, 0]
        hit = ok & (points <= iv[i, 1]) & (dur < best)
        best[hit] = dur[hit]
        label[hit] = name
    return list(label)
