"""Find a cell's pieces by name: its configuration, traffic mix, limits and
per-layer metric readers.

``BENCHMARK.json`` names a cell's configuration and traffic mix. A
configuration is ``bench/configs/<name>.json``, a traffic mix is
``bench/traffic/<name>.json`` (its ``driver`` key says whether it trains or
serves), the limits of the cell's correctness check are
``bench/limits/<cell>.json``, a per-layer metric is
``bench/metrics/<name>.py`` and a configuration's model kind is a module
of ``bench/kinds/``. A later cell adds files and edits none.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

from bench import kinds

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def cell(name: str) -> dict:
    for w in benchmark()["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"no workload named {name!r} in BENCHMARK.json")


def config_file(name: str) -> dict:
    return load_json(BENCH_DIR / "configs" / f"{name}.json")


def traffic(name: str) -> dict:
    return load_json(BENCH_DIR / "traffic" / f"{name}.json")


def limits(cell_name: str) -> dict:
    return load_json(BENCH_DIR / "limits" / f"{cell_name}.json")


def peaks(device_kind: str) -> dict:
    table = load_json(BENCH_DIR / "peaks.json")["devices"]
    if device_kind not in table:
        raise SystemExit(f"no peaks for device kind {device_kind!r} in "
                         f"bench/peaks.json; add them with their source")
    return table[device_kind]


def arch_config(name: str):
    """The program's ``ArchConfig`` for configuration ``name``, as run."""
    return program_config(config_file(name))


def program_config(f: dict):
    """The program's ``ArchConfig`` for configuration file ``f``: the
    registered config of ``f["arch"]`` with the file's depth, vocabulary,
    numerics and ``program`` settings. Widths are never changed: each of
    the kind's ``WIDTHS`` must agree with the registered config."""
    from repro import configs

    base = configs.get(f["arch"])
    k = kinds.kind(f)
    for key, field in k.WIDTHS:
        if f[key] != getattr(base, field):
            raise SystemExit(f"{f['arch']}: {key}={f[key]} but the "
                             f"program's {field} is {getattr(base, field)}")
    over = k.overrides(f)
    over.update(f.get("program", {}))
    return dataclasses.replace(base, **over)


def metric_reader(name: str):
    """The ``read(ctx)`` function of ``bench/metrics/<name>.py``."""
    path = BENCH_DIR / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
