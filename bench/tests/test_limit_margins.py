"""The margins of each cell's correctness limits over the chip readings
they were set from (``bench/limits/readings/<cell>.json``, made by
``bench/calibrate.py``): a run draws seeds that calibration never saw, so
no limit sits at the edge of what the program read, and neither the
control nor a fault passes by a hair.

    JAX_PLATFORMS=cpu python -m pytest bench/tests -n 6 --dist loadfile
"""

from __future__ import annotations

import json
from pathlib import Path

import pytest

from bench import spec

READINGS = sorted((Path(__file__).resolve().parents[1] / "limits"
                   / "readings").glob("*.json"))
MARGIN = 1.5


@pytest.mark.parametrize("path", READINGS, ids=lambda p: p.stem)
def test_each_limit_stands_well_above_every_program_reading(path):
    rows = json.loads(path.read_text())
    for key, limit in spec.limits(path.stem).items():
        top = max(r[key] for r in rows if r["kind"] == "program")
        assert limit >= MARGIN * top, (key, limit, top)


def _judged(path: Path) -> list[tuple[Path, str]]:
    """(readings, kind) for each kind of reading other than the program's:
    the control and each fault are judged apart, so that one that stands
    too near the limits shows by name."""
    kinds = {r["kind"] for r in json.loads(path.read_text())}
    assert {"program", "control"} <= kinds, path
    return [(path, k) for k in sorted(kinds - {"program"})]


@pytest.mark.parametrize("path,kind", [c for p in READINGS for c in _judged(p)],
                         ids=lambda c: c.stem if isinstance(c, Path) else c)
def test_every_control_and_fault_reading_exceeds_a_limit_well(path, kind):
    rows = [r for r in json.loads(path.read_text()) if r["kind"] == kind]
    limits = spec.limits(path.stem)
    for r in rows:
        assert max(r[k] / v for k, v in limits.items()) >= MARGIN, r
