"""Helpers the per-layer metric readers share."""

from __future__ import annotations

import numpy as np

from bench import trace as trace_mod


def module_seconds(red, tag: str) -> list[float]:
    """Device seconds of each execution of the program named ``tag``."""
    return [(e - s) * 1e-9 for n, s, e in red.modules if tag in n]


def matched(red, tag: str, works: list):
    """(seconds, works) of a program's executions in the traced window;
    None if it ran none there. The window holds every call the harness
    made, so the counts must agree."""
    secs = module_seconds(red, tag)
    if not works or not secs:
        return None
    if len(secs) != len(works):
        raise ValueError(f"{len(secs)} executions of {tag!r} in the trace, "
                         f"{len(works)} made")
    return np.asarray(secs), works


def idle_share_in(red, span_name: str):
    """Idle percent of the device inside the host spans ``span_name``."""
    iv = [(a, b) for n, a, b in red.spans if n == span_name]
    if not iv:
        return None
    total = sum(b - a for a, b in iv)
    busy = np.mean([sum(trace_mod.covered(bc, a, b) for a, b in iv)
                    for bc in red.busy])
    return 100.0 * (1.0 - busy / total)
