"""Find a cell's pieces by name: its configuration, traffic mix, limits and
per-layer metric readers.

``BENCHMARK.json`` names a cell's configuration and traffic mix. A
configuration is ``bench/configs/<name>.json``, a traffic mix is
``bench/traffic/<name>.json`` (its ``driver`` key says whether it trains or
serves), the limits of the cell's correctness check are
``bench/limits/<cell>.json`` and a per-layer metric is
``bench/metrics/<name>.py``. A later cell adds files and edits none.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

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


# (file key, ArchConfig field) pairs that must agree with the program's
# registered config: widths are never changed by the benchmark.
_WIDTHS = {
    "granitemoe": [("hidden_size", "d_model"), ("intermediate_size", "moe_d_ff"),
                   ("num_attention_heads", "num_heads"),
                   ("num_key_value_heads", "num_kv_heads"),
                   ("num_local_experts", "moe_num_experts"),
                   ("num_experts_per_tok", "moe_top_k")],
    "mamba2": [("d_model", "d_model"), ("d_state", "ssm_state"),
               ("d_conv", "ssm_conv"), ("expand", "ssm_expand"),
               ("headdim", "ssm_head_dim"), ("ngroups", "ssm_groups")],
}


def arch_config(name: str):
    """The program's ``ArchConfig`` for configuration ``name``, as run."""
    from repro import configs

    f = config_file(name)
    base = configs.get(f["arch"])
    kind = f.get("model_type", "mamba2")
    for key, field in _WIDTHS[kind]:
        if f[key] != getattr(base, field):
            raise SystemExit(f"{name}: {key}={f[key]} but the program's "
                             f"{field} is {getattr(base, field)}")
    prog = f.get("program", {})
    if kind == "granitemoe":
        over = dict(num_layers=f["num_hidden_layers"],
                    vocab_size=f["vocab_size"], rope_theta=f["rope_theta"],
                    norm_eps=f["rms_norm_eps"],
                    tie_embeddings=f["tie_word_embeddings"],
                    dtype=f["torch_dtype"])
    else:
        over = dict(num_layers=f["n_layer"], vocab_size=f["vocab_size"],
                    ssm_chunk=f["chunk_size"], norm_eps=f["norm_eps"],
                    tie_embeddings=f["tie_embeddings"], dtype=f["dtype"])
    over.update(prog)
    return dataclasses.replace(base, **over)


def metric_reader(name: str):
    """The ``read(ctx)`` function of ``bench/metrics/<name>.py``."""
    path = BENCH_DIR / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
