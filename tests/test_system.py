"""End-to-end behaviour tests: training convergence, checkpoint/restart
equivalence, serving loop, sharding engine fit rules, dry-run cell
plumbing (single-device)."""

import dataclasses
import os
import tempfile
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.checkpoint import CheckpointManager
from repro.data import PipelineConfig, TokenPipeline
from repro.launch import train as train_mod
from repro.launch import serve as serve_mod
from repro.models import model
from repro.optim import AdamWConfig, adamw_init, adamw_update


@pytest.mark.slow
def test_training_reduces_loss_end_to_end():
    """A tiny LM must overfit the deterministic synthetic stream."""
    loss = train_mod.main([
        "--arch", "stablelm-1.6b", "--reduced", "--steps", "60",
        "--global-batch", "8", "--seq-len", "32", "--lr", "3e-3",
        "--warmup", "10", "--log-every", "30"])
    # well below ln(V) = ln(256) ≈ 5.55 after 60 steps
    assert loss < 5.0


@pytest.mark.slow
def test_checkpoint_restart_bitwise_resume():
    """Stop at step k, restart, and land on the same loss trajectory."""
    cfg = configs.get("qwen2.5-3b").reduced()
    pipe = TokenPipeline(PipelineConfig(vocab_size=cfg.vocab_size,
                                        seq_len=16, global_batch=4, seed=5))
    opt_cfg = AdamWConfig(lr_peak=1e-3, warmup_steps=2, total_steps=30)

    def _step(p, o, b):
        (l, _), g = jax.value_and_grad(
            lambda pp: model.train_loss(pp, cfg, b), has_aux=True)(p)
        p, o, _ = adamw_update(g, o, p, opt_cfg)
        return p, o, l

    step_fn = jax.jit(_step)

    def run(start, steps, params, opt):
        losses = []
        for s in range(start, start + steps):
            b = pipe.batch_at(s)
            params, opt, l = step_fn(params, opt, b)
            losses.append(float(l))
        return params, opt, losses

    params = model.init_params(cfg, jax.random.PRNGKey(1))
    opt = adamw_init(params, opt_cfg)

    # uninterrupted run
    _, _, ref_losses = run(0, 10, params, opt)

    # interrupted at 6 + resume from checkpoint
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d)
        p2, o2, l_a = run(0, 6, params, opt)
        mgr.save_sync(6, {"params": p2, "opt": o2})
        step, tree = mgr.restore_latest({"params": p2, "opt": o2})
        assert step == 6
        _, _, l_b = run(6, 4, tree["params"], tree["opt"])
    np.testing.assert_allclose(l_a + l_b, ref_losses, rtol=2e-4, atol=2e-5)


def test_serving_driver_runs():
    res = serve_mod.main(["--arch", "qwen2.5-3b", "--reduced",
                          "--batch", "2", "--prompt-len", "16",
                          "--gen", "4"])
    gen = res.tokens
    assert gen.shape == (2, 4)
    assert (gen >= 0).all() and (gen < 256).all()
    assert res.prefill_logits.shape == (2, 1, 256)
    assert np.isfinite(res.prefill_logits).all()


@pytest.mark.slow
def test_moe_arch_trains_with_steal_table():
    loss = train_mod.main([
        "--arch", "granite-moe-1b-a400m", "--reduced", "--steps", "30",
        "--global-batch", "4", "--seq-len", "32", "--lr", "2e-3",
        "--warmup", "5", "--log-every", "15"])
    assert np.isfinite(loss) and loss < 5.55


# ----------------------------------------------------------------------
# sharding rules engine (pure functions — no extra devices needed)
# ----------------------------------------------------------------------

class _FakeMesh:
    """Duck-typed mesh for fit_spec tests."""
    def __init__(self, shape):
        self.shape = shape
        self.axis_names = tuple(shape)


def test_fit_spec_divisibility_fallback():
    from jax.sharding import PartitionSpec as P
    from repro.launch.shardings import fit_spec
    mesh = _FakeMesh({"data": 16, "model": 16})
    # divisible → kept
    assert tuple(fit_spec(mesh, (256, 512), P("data", "model"))) == \
        ("data", "model")
    # non-divisible dim → replicated
    assert tuple(fit_spec(mesh, (40, 512), P("model", "data"))) == \
        (None, "data")


def test_fit_spec_trailing_none_trimmed():
    from jax.sharding import PartitionSpec as P
    from repro.launch.shardings import fit_spec
    mesh = _FakeMesh({"data": 4})
    p = fit_spec(mesh, (8, 3, 5), P("data", None, None))
    assert tuple(p) == ("data",)


def test_input_specs_cover_every_cell():
    from repro.launch import dryrun
    for arch in configs.ARCHS:
        cfg = configs.get(arch)
        for shape in cfg.shapes():
            sds = dryrun.input_specs(arch, shape)
            assert isinstance(sds, dict) and sds
            for v in jax.tree.leaves(sds):
                assert isinstance(v, jax.ShapeDtypeStruct)


def test_skipped_cells_documented():
    total = 0
    for arch in configs.ARCHS:
        cfg = configs.get(arch)
        run = set(cfg.shapes())
        skip = set(cfg.skipped_shapes())
        assert run.isdisjoint(skip)
        assert run | skip == set(configs.SHAPES)
        total += len(run)
    assert total == 31      # 40 cells − 9 documented skips


# ----------------------------------------------------------------------
# persistent compilation cache placement
# ----------------------------------------------------------------------

@pytest.mark.parametrize("env_dir", [None, "elsewhere"])
def test_compile_cache_placement(monkeypatch, tmp_path, env_dir):
    from repro.launch import jax_cache
    before = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        repo = Path(configs.__file__).resolve().parents[3]
        assert jax_cache.REPO_CACHE_DIR == repo / ".jax_cache"
        want = str(jax_cache.REPO_CACHE_DIR)
    else:
        want = str(tmp_path / env_dir)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
    try:
        assert jax_cache.use_persistent_compile_cache() == want
        # with the variable set, the location is JAX's to read
        assert jax.config.jax_compilation_cache_dir == \
            (before if env_dir else want)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
