#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``bench/configs/<name>.json``) and a traffic mix
(``bench/traffic/<name>.json``, whose ``driver`` is ``train`` or
``serve``). The run makes its weights and inputs from ``--seed``, warms
up the cell's shapes (set-up), measures for ``--seconds``, then checks
what the timed path produced against the plain reference. The last line
of stdout is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (end-to-end with ``--trace 0``, per-layer with ``--trace
1``), ``device``, with ``--trace 1`` ``breakdown``, and last ``checks``:
each compared number beside its limit. Without a TPU, or with fewer chips
than the cell asks for, it exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def preflight(chips: int) -> dict:
    """The TPU this run measures, or exit non-zero."""
    import jax

    if jax.default_backend() != "tpu":
        raise SystemExit(f"bench: no TPU (JAX's backend is "
                         f"{jax.default_backend()!r}); nothing measured")
    from bench import harness

    dev = harness.device_info()
    if dev["count"] < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX sees "
                         f"{dev['count']}")
    return dev


def compile_cache():
    """JAX's persistent cache at its fixed place in the checkout; every
    program cached, however fast it compiled."""
    import jax
    from repro.launch.jax_cache import use_persistent_compile_cache

    use_persistent_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def run_cell(name: str, seed: int, seconds: float, traced: bool,
             device: dict) -> dict:
    """Run a cell and return its result line (without printing it)."""
    import importlib

    from bench import harness, spec

    cell = spec.cell(name)
    fcfg = spec.config_file(cell["config"])
    mix = spec.traffic(cell["traffic"])
    driver = importlib.import_module(f"bench.drivers.{mix['driver']}")
    res = driver.run(spec.arch_config(cell["config"]), fcfg, mix, seed,
                     seconds, traced, spec.limits(name), cell, T_START)

    dev = dict(device, memory_peak_bytes=res["memory_peak"])
    line = {"correct": res["correct"], "attempted": res["attempted"],
            "failed": res["failed"]}
    if traced:
        red = res["ctx"]["trace"]
        res["ctx"]["peaks"] = spec.peaks(device["kind"])
        line["metrics"] = harness.per_layer(cell, res["ctx"])
        dev.update(busy_s=red.busy_s, window_s=red.window_s)
        line["device"] = dev
        # an op's name is its HLO instruction: keep the name and shape
        line["breakdown"] = {
            "device_ops": [[n[:160], s] for n, s in red.device_ops],
            "idle_gaps": [list(x) for x in red.idle_gaps]}
    else:
        line["metrics"] = dict(res["metrics"], setup_s={
            "value": res["setup_s"], "unit": "s"})
        line["device"] = dev
    harness.write_json(f"{name}.{seed}.{int(traced)}.numbers.json",
                       res["numbers"])
    line["checks"] = harness.check_lines(res["checks"])
    return line


def main(argv=None):
    args = parse(argv)
    from bench import harness, spec

    cell = spec.cell(args.workload)
    device = preflight(cell["chips"])
    compile_cache()
    harness.emit(run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), device))


if __name__ == "__main__":
    main()
