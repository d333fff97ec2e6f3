"""CPU tests of the benchmark's own code: generators, operation counts,
trace reduction, the references against the program at small sizes, the
correctness check against broken timed paths, and the chip preflight.

    JAX_PLATFORMS=cpu python -m pytest bench/tests -n 6 --dist loadfile
"""

from __future__ import annotations

import importlib
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import small
from bench import flops, kinds, spec, trace, weights
from bench.reference import model as ref_model
from bench.traffic import gen

ROOT = Path(__file__).resolve().parents[2]
TESTDATA = ROOT / "bench" / "testdata"


def _cells(driver: str) -> list[str]:
    """The benchmark's cells whose traffic mix runs ``driver``."""
    return [w["name"] for w in spec.benchmark()["workloads"]
            if spec.traffic(w["traffic"])["driver"] == driver]


TRAIN_CELLS, SERVE_CELLS = _cells("train"), _cells("serve")


# ---------------------------------------------------------------- traffic

def test_train_batches_repeat_per_seed_and_mask_eos():
    mix = small.mix("train-zipf-8x1024", seq_len=512)
    a = gen.train_batch(mix, 1000, 2**40 + 3, 1)
    b = gen.train_batch(mix, 1000, 2**40 + 3, 1)
    c = gen.train_batch(mix, 1000, 2**40 + 4, 1)
    assert all((a[k] == b[k]).all() for k in a)
    assert (a["tokens"] != c["tokens"]).any()
    assert a["tokens"].shape == (mix["batch"], mix["seq_len"])
    # labels are the next token, -100 where that token is EOS
    nxt = a["tokens"][:, 1:]
    lab = a["labels"][:, :-1]
    assert ((lab == -100) == (nxt == 0)).all()
    assert (lab[lab >= 0] == nxt[lab >= 0]).all()
    assert (a["tokens"] == 0).any(), "documents are separated by EOS"


def test_zipf_puts_its_mass_on_the_top_ids():
    z = gen.Zipf(7, 50_000, 1.0)
    ids = z.draw(gen.rng(7, 0), 200_000)
    top = z.ids[:100]
    share = np.isin(ids, top).mean()
    # H(100) / H(49999) for s = 1
    want = np.sum(1 / np.arange(1, 101)) / np.sum(1 / np.arange(1, 50_000))
    assert abs(share - want) < 0.01
    assert (ids > 0).all(), "EOS (id 0) is never drawn"


def test_serve_deck_holds_the_same_mix_every_cycle():
    mix = spec.traffic("chat-azure-conv")
    n = sum(mix["deck"])
    lens = [gen.serve_prompt_len(mix, i) for i in range(8 * n)]
    for c in range(8):
        cyc = lens[c * n:(c + 1) * n]
        assert cyc == lens[:n]
        assert sorted(cyc) == sorted(
            L for L, k in zip(mix["prompt_lens"], mix["deck"])
            for _ in range(k))
    a = gen.serve_batch(mix, 1000, 9, 3)
    assert (a == gen.serve_batch(mix, 1000, 9, 3)).all()
    assert a.shape == (mix["batch"], gen.serve_prompt_len(mix, 3))


# ---------------------------------------------------------------- counts

def _kind(name: str):
    return kinds.kind(spec.config_file(name))


def test_flops_by_hand_for_one_granite_layer():
    cfg = spec.arch_config("granite-moe-1b-a400m")
    B, S, D, H, Hkv, Dh = 2, 16, 1024, 16, 8, 64
    att = flops.attention(B, S, 0, D, H, Hkv, Dh)
    proj = 2 * B * S * D * (2 * H * Dh + 2 * Hkv * Dh)
    assert att.flops == proj + 4 * B * H * Dh * (S * (S + 1) // 2)
    moe = flops.moe_experts(B * S, D, 512, 32, 8)
    assert moe.flops == 2 * B * S * D * 32 + 2 * B * S * 8 * 3 * D * 512
    layer = (flops.forward(_kind("granite-moe-1b-a400m"), cfg, B, S).flops
             - flops.head(B * S, D, cfg.vocab_size).flops
             - flops.rmsnorm(B * S, D).flops) / cfg.num_layers
    assert layer == att.flops + moe.flops + 2 * flops.rmsnorm(B * S, D).flops


def test_flops_by_hand_for_one_mamba2_layer():
    cfg = spec.arch_config("mamba2-1.3b")
    B, S, D = 1, 8, 2048
    di, H, N, P, K = 4096, 64, 128, 64, 4
    w_in, w_out = D * (2 * di + 2 * N + H), di * D
    want = (2 * B * S * (w_in + w_out) + 2 * B * S * K * (di + 2 * N)
            + 6 * B * S * di + 5 * B * S * H * N * P)
    got = flops.mamba_mixer(B, S, D, di, H, N, P, 1, K, False).flops
    assert got == want
    layer = (flops.forward(_kind("mamba2-1.3b"), cfg, B, S).flops
             - flops.head(B * S, D, cfg.vocab_size).flops
             - flops.rmsnorm(B * S, D).flops) / cfg.num_layers
    assert layer == want + flops.rmsnorm(B * S, D).flops


def test_decode_bytes_hold_every_weight_and_the_live_cache():
    cfg = spec.arch_config("granite-moe-1b-a400m")
    from repro.models import model as model_lib
    n_params = model_lib.param_count(cfg)
    w = flops.decode_step(_kind("granite-moe-1b-a400m"), cfg, 8, 1000)
    kv = cfg.num_layers * 2 * 8 * 8 * 64 * 2 * 1001
    # every weight once (the router in f32), the cache, small activations
    assert n_params * 2 + kv < w.bytes < n_params * 2 + kv + 50e6


# ---------------------------------------------------------------- trace

def _small_trace():
    path = TESTDATA / "small.xplane.pb"
    if not path.exists():
        pytest.skip("no recorded device trace in bench/testdata")
    return trace.load(path)


def test_trace_reduction_of_a_recorded_chip_trace():
    got = trace.reduce(_small_trace())
    pinned = json.loads((TESTDATA / "small.pinned.json").read_text())
    assert got.window_s == pytest.approx(pinned["window_s"], rel=1e-9)
    assert got.busy_s == pytest.approx(pinned["busy_s"], rel=1e-9)
    assert [n for n, _ in got.device_ops] == pinned["top_ops"]
    assert sum(s for _, s in got.idle_gaps) == pytest.approx(
        got.window_s - got.busy_s, rel=1e-6)
    assert len(got.modules) == pinned["modules"]
    assert dict(got.idle_gaps) == pytest.approx(pinned["idle_gaps"])


def test_merge_and_labels():
    iv = np.array([[5, 7], [0, 2], [1, 3], [6, 9], [10, 11]], float)
    assert trace.merge(iv).tolist() == [[0, 3], [5, 9], [10, 11]]
    spans = [("bench.window", 0, 20), ("decode", 0, 10),
             ("token_fetch", 4, 6), ("token_fetch", 8, 9)]
    assert trace.label_points(spans, np.array([1., 5, 8.5, 15])) == [
        "decode", "token_fetch", "token_fetch", "other"]
    tr = trace.Trace([trace.Chip(np.array([[2., 4], [3, 6], [12, 13]]),
                                 ["a", "b", "a"], np.zeros((0, 2)), [])],
                     spans)
    red = trace.reduce(tr)
    assert red.busy_s == pytest.approx(5e-9)
    assert red.window_s == pytest.approx(20e-9)
    assert dict(red.idle_gaps) == pytest.approx(
        {"decode": 5e-9, "token_fetch": 1e-9, "other": 9e-9})


# ---------------------------------------------------------------- the files

def test_every_cell_finds_its_files_by_name():
    b = spec.benchmark()
    names = {c["name"] for c in b["configs"]}
    for c in b["configs"]:
        f = spec.config_file(c["name"])
        assert c["file"] == f"bench/configs/{c['name']}.json"
        assert sorted(c["reduced"]) == sorted(f["reduced"])
        assert f["source"] == c["source"]
    for w in b["workloads"]:
        assert w["config"] in names
        assert spec.traffic(w["traffic"])["driver"] in ("train", "serve")
        assert spec.limits(w["name"])
        assert importlib.import_module(
            f"bench.drivers.{spec.traffic(w['traffic'])['driver']}")
    for m in b["per_layer"]:
        assert callable(spec.metric_reader(m["name"]))
    for kind in ("TPU v5 lite",):
        assert spec.peaks(kind)["flops_per_s"] == 197e12


def test_reference_imports_nothing_of_the_program():
    for p in [*(ROOT / "bench" / "reference").glob("*.py"),
              *(ROOT / "bench" / "kinds").glob("*.py")]:
        text = p.read_text()
        assert "repro" not in text, p


# ---------------------------------------------------------------- references

def test_reference_routing_matches_the_program_with_overflow():
    from repro.core.routing import RoutingConfig, route

    s = ref_model.Spec(layers=1, D=8, V=8, eps=1e-6, E=32, K=8,
                       capacity_factor=0.3, steal_attempts=2)
    table = ref_model.torus_table(32)
    logits = jax.random.normal(jax.random.PRNGKey(3), (256, 32)) * 2
    cap = ref_model.capacity(s, 256)
    comb, aux = ref_model.route(logits, s, cap, table)
    r = route(logits, RoutingConfig(32, 8, cap, 2, "dfwspt"), table)
    want = jnp.einsum("tk,tke->te", r["weight"],
                      jax.nn.one_hot(r["expert"], 32))
    assert float(r["drop_fraction"]) > 0, "the case must overflow"
    np.testing.assert_allclose(comb, want, atol=1e-6)
    np.testing.assert_allclose(aux, r["aux_loss"], rtol=1e-6)


def test_torus_table_is_the_programs_nearest_victim_order():
    from repro.launch.mesh import line_steal_table

    cfg = spec.arch_config("granite-moe-1b-a400m-l6")
    got = line_steal_table(cfg.moe_num_experts, cfg.moe_steal_policy)
    assert (got == ref_model.torus_table(32)).all()


@pytest.mark.parametrize("name", ["granite-moe-1b-a400m-l6",
                                  "mamba2-1.3b-l12"])
def test_reference_forward_matches_the_program(name):
    from repro.models import model as model_lib

    cfg, f = small.configs(name)
    kind = kinds.kind(f)
    s = kind.spec_from_file(f)
    params = weights.make_init(weights.layout(cfg), kind)(
        weights.seed_key(11))
    tokens = jnp.asarray(gen.train_batch(small.mix("train-zipf-8x1024"),
                                         cfg.vocab_size, 11, 0)["tokens"])
    table = kind.train_table(s)
    with jax.default_matmul_precision("highest"):
        want, _ = model_lib.forward(params, cfg, tokens=tokens,
                                    steal_table=table)
        got = small.reference_logits(params, kind, s, tokens, table)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_control_rounds_to_fp8():
    x = jnp.linspace(-1.0, 1.0, 1001)
    q = ref_model.Numerics(fp8=True).q(x)
    assert 0 < float(jnp.max(jnp.abs(q - x))) < 1.0 / 16
    assert len(np.unique(np.asarray(q))) < 256


# ---------------------------------------------------------------- faults

def _run(cell: str, seconds: float = 0.5) -> dict:
    c = spec.cell(cell)
    cfg, f = small.configs(c["config"])
    mix = small.mix(c["traffic"])
    drv = importlib.import_module(f"bench.drivers.{mix['driver']}")
    return drv.run(cfg, f, mix, 2**35 + 17, seconds, False,
                   spec.limits(cell), c, time.perf_counter())


@pytest.mark.parametrize("cell", TRAIN_CELLS + SERVE_CELLS)
def test_a_sound_run_is_correct(cell):
    res = _run(cell)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0
    assert all(m["value"] > 0 for m in res["metrics"].values())


@pytest.mark.parametrize("cell", TRAIN_CELLS)
def test_a_step_that_leaves_the_state_unchanged_is_caught(cell, monkeypatch):
    from repro.launch import train as program_train

    update = program_train.adamw_update

    def frozen(grads, state, params, cfg):
        _, new_state, m = update(grads, state, params, cfg)
        return params, new_state, m

    monkeypatch.setattr(program_train, "adamw_update", frozen)
    res = _run(cell)
    assert not res["correct"]
    assert res["numbers"]["change_gap"] == pytest.approx(1.0)


@pytest.mark.parametrize("cell", TRAIN_CELLS)
def test_a_step_over_half_the_batch_is_caught(cell):
    from bench import calibrate

    undo = calibrate.half_batch()
    try:
        res = _run(cell)
    finally:
        undo()
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("cell", SERVE_CELLS)
def test_an_altered_token_is_caught(cell, monkeypatch):
    from repro.models import model as model_lib

    decode = model_lib.decode_step

    def altered(params, cfg, caches, tokens, steal_table=None):
        # every token becomes the one the model ranks last
        logits, caches = decode(params, cfg, caches, tokens, steal_table)
        return -logits, caches

    monkeypatch.setattr(model_lib, "decode_step", altered)
    res = _run(cell)
    assert not res["correct"]


# ---------------------------------------------------------------- preflight

def test_run_without_a_tpu_exits_nonzero_and_prints_nothing():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(ROOT / "bench" / "run.py"),
                        "--workload", TRAIN_CELLS[0], "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, timeout=120)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "no TPU" in p.stderr


# ---------------------------------------------------------------- control

@pytest.mark.parametrize("cell", TRAIN_CELLS)
def test_the_control_stands_apart_from_the_program(cell):
    """The reference in fp8 in the program's place reads far above the
    program on a compared number (f32 on the CPU, so the program matches
    the reference to rounding); against the limits it is read on the chip
    at the cell's size (PERF.md)."""
    from bench import calibrate, check

    c = spec.cell(cell)
    cfg, f = small.configs(c["config"])
    rows = dict(calibrate.train_readings(cfg, f, small.mix(c["traffic"]),
                                         2**33 + 1, True, None))
    limits = spec.limits(cell)
    assert check.judge(rows["program"], limits)[0]
    assert max(rows["control"][k] / max(rows["program"][k], 1e-9)
               for k in limits) > 1e4, rows["control"]


@pytest.mark.parametrize("cell", SERVE_CELLS)
def test_the_control_moves_served_tokens(cell):
    """At this size the program matches the reference exactly (f32 on the
    CPU) and the fp8 control does not; its gap against the limit is read
    on the chip at the cell's size (PERF.md)."""
    from bench import calibrate

    c = spec.cell(cell)
    cfg, f = small.configs(c["config"])
    rows = dict(calibrate.serve_readings(cfg, f, small.mix(c["traffic"]),
                                         2**33 + 1, True, None))
    assert rows["program"]["logit_gap"] < 1e-4
    assert rows["control"]["logit_gap"] > 100 * max(
        rows["program"]["logit_gap"], 1e-5)
    assert rows["control"]["_mismatch_share"] > 0


READINGS = sorted((ROOT / "bench" / "limits" / "readings").glob("*.json"))


@pytest.mark.parametrize("path", READINGS, ids=lambda p: p.stem)
def test_the_limits_lie_between_the_chip_readings(path):
    """The readings a cell's limits were set from, taken on the chip at the
    cell's size (bench/calibrate.py): judged as a run is, every reading of
    the program passes the committed limits, and every reading of the
    control and of each fault fails them."""
    from bench import check

    rows = json.loads(path.read_text())
    limits = spec.limits(path.stem)
    programs = {r["seed"] for r in rows if r["kind"] == "program"}
    assert len(programs) >= 12
    assert sum(r["kind"] == "control" for r in rows) >= 3
    for r in rows:
        assert check.judge(r, limits)[0] == (r["kind"] == "program"), r
