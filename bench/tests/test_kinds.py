"""CPU tests of the model kinds (``bench/kinds/``): a kind that comes only
as a file, and what the lookup must not move for the benchmark's own.

    JAX_PLATFORMS=cpu python -m pytest bench/tests -n 6 --dist loadfile
"""

from __future__ import annotations

import copy
import hashlib
import sys
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import small
from bench import flops, kinds, spec, weights
from bench.reference import run as ref_run
from bench.traffic import gen

TEST_KINDS = Path(__file__).resolve().parent / "kinds"

# The program's jamba-1.5-large-398b as the test kind reads it.
JAMBA = {
    "arch": "jamba-1.5-large-398b", "model_type": "jamba",
    "hidden_size": 8192, "intermediate_size": 24576,
    "moe_intermediate_size": 24576, "num_hidden_layers": 72,
    "num_attention_heads": 64, "num_key_value_heads": 8,
    "num_experts": 16, "num_experts_per_tok": 2,
    "mamba_d_state": 128, "mamba_d_conv": 4, "mamba_expand": 2,
    "mamba_headdim": 64, "mamba_ngroups": 1, "mamba_chunk_size": 128,
    "attn_layer_period": 8, "attn_layer_offset": 0,
    "expert_layer_period": 2, "expert_layer_offset": 1,
    "vocab_size": 65536, "tie_word_embeddings": False,
    "rms_norm_eps": 1e-5, "rope_theta": 1e6, "torch_dtype": "bfloat16",
    "program": {"capacity_factor": 1.25, "moe_group": 4096,
                "moe_steal_attempts": 2, "router_aux_weight": 0.01,
                "z_loss_weight": 1e-4},
}


@pytest.fixture
def jamba(monkeypatch):
    """The test's kind module, found by the lookup on its search path."""
    monkeypatch.setattr(kinds, "__path__", [*kinds.__path__, str(TEST_KINDS)])
    yield kinds.kind(JAMBA)
    sys.modules.pop("bench.kinds.jamba")
    del kinds.jamba


@pytest.fixture
def small_jamba(jamba):
    """(kind, program config, file, reference spec, weights) at the
    program's reduced() size."""
    from repro import configs as pc

    f = copy.deepcopy(JAMBA)
    cfg, f = jamba.small(f, pc.get(f["arch"]).reduced())
    params = weights.make_init(weights.layout(cfg), jamba)(
        weights.seed_key(2**36 + 5))
    return jamba, cfg, f, jamba.spec_from_file(f), params


def _batch(cfg, i: int) -> dict:
    b = gen.train_batch(small.mix("train-zipf-8x1024"), cfg.vocab_size,
                        2**36 + 5, i)
    return {k: jnp.asarray(v) for k, v in b.items()}


# ---------------------------------------------------------------- a new kind

def test_a_kind_not_on_the_search_path_is_an_error():
    with pytest.raises(ModuleNotFoundError, match="bench.kinds.jamba"):
        kinds.kind(JAMBA)


def test_the_lookup_finds_a_kind_outside_the_package(jamba):
    assert Path(jamba.__file__).parent == TEST_KINDS
    assert jamba.__name__ == "bench.kinds.jamba"
    cfg = spec.program_config(JAMBA)
    assert cfg.num_layers == 72 and not cfg.tie_embeddings
    assert kinds.kind({}).__name__ == "bench.kinds.mamba2"


def test_a_kind_keeps_the_widths_of_the_program(jamba):
    f = dict(JAMBA, mamba_d_state=64)
    with pytest.raises(SystemExit, match="mamba_d_state"):
        spec.program_config(f)


def test_a_hybrid_period_matches_the_program(small_jamba):
    from repro.models import model as model_lib

    kind, cfg, _, s, params = small_jamba
    assert len(cfg.pattern) == 8 and cfg.repeats == 2
    assert kind.period(s) == list(cfg.pattern)
    assert {m for m, _ in cfg.pattern} == {"attn", "mamba"}
    assert {g for _, g in cfg.pattern} == {"mlp", "moe"}
    assert "lm_head" in params
    tokens = _batch(cfg, 0)["tokens"]
    table = kind.train_table(s)
    with jax.default_matmul_precision("highest"):
        want, _ = model_lib.forward(params, cfg, tokens=tokens,
                                    steal_table=table)
        got = small.reference_logits(params, kind, s, tokens, table)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_served_logits_run_every_slot_of_a_hybrid_period(small_jamba):
    """Slot by slot, the serve path's reference equals the whole forward
    at the last prompt position."""
    kind, cfg, _, s, params = small_jamba
    tokens = _batch(cfg, 1)["tokens"]
    L, table = tokens.shape[1], kind.serve_table(s)
    got = ref_run.serve_logits(params, kind, s, np.asarray(tokens), L, table)
    with jax.default_matmul_precision("highest"):
        want = small.reference_logits(params, kind, s, tokens, table)
    np.testing.assert_allclose(got, want[:, L - 1:], rtol=1e-5, atol=1e-5)


def test_the_reference_trains_every_slot_of_a_hybrid_period(small_jamba):
    from repro.models import model as model_lib

    kind, cfg, _, s, params = small_jamba
    batches = [_batch(cfg, i) for i in range(2)]
    opt = {k: small.mix("train-zipf-8x1024")[k] for k in (
        "lr", "warmup_steps", "total_steps", "lr_min_ratio", "b1", "b2",
        "eps", "weight_decay", "clip_norm")}
    table = kind.train_table(s)
    res = ref_run.train_steps(params, kind, s, batches, opt, table)
    assert np.isfinite(res["losses"]).all()
    with jax.default_matmul_precision("highest"):
        want, _ = model_lib.train_loss(params, cfg, batches[0], table)
    np.testing.assert_allclose(res["losses"][0], want, rtol=2e-4)
    for slot in range(len(cfg.pattern)):
        grads = [g for n, g in res["grad"].items()
                 if n.startswith(f"['blocks'][{slot}]")]
        assert grads and np.isfinite(grads).all() and min(grads) > 0, slot
    assert res["grad"]["['lm_head']"] > 0


def test_the_work_counts_each_slot_of_a_hybrid_period(small_jamba):
    kind, cfg, *_ = small_jamba
    B, S, D = 4, 32, cfg.d_model
    T, d_inner = B * S, cfg.ssm_expand * D
    attn = flops.attention(B, S, 0, D, cfg.num_heads, cfg.num_kv_heads,
                           cfg.head_dim)
    ssm = flops.mamba_mixer(B, S, D, d_inner, d_inner // cfg.ssm_head_dim,
                            cfg.ssm_state, cfg.ssm_head_dim, cfg.ssm_groups,
                            cfg.ssm_conv, False)
    experts = flops.moe_experts(T, D, cfg.moe_d_ff, cfg.moe_num_experts,
                                cfg.moe_top_k)
    mlp = flops.Work(6 * T * D * cfg.d_ff,
                     3 * D * cfg.d_ff * flops.W + 2 * T * D * flops.W)
    norms = flops.rmsnorm(T, D) * 2
    period = [attn + mlp, ssm + experts, ssm + mlp, ssm + experts,
              ssm + mlp, ssm + experts, ssm + mlp, ssm + experts]
    layers = sum((w + norms for w in period), flops.Work()) * cfg.repeats
    fwd = (layers + flops.rmsnorm(T, D) + flops.head(T, D, cfg.vocab_size)
           + flops.cross_entropy(T, cfg.vocab_size))
    got = flops.train_step(kind, cfg, B, S)
    assert (got.flops, got.bytes) == (3 * fwd.flops, 3 * fwd.bytes)


def test_a_kind_draws_leaf_names_of_its_own():
    abstract = {"blocks": [{"gate_bias": jax.ShapeDtypeStruct(
        (2, 8), jnp.bfloat16)}]}

    class Kind:
        WEIGHTS = {"gate_bias": lambda key, shape: jnp.full(shape, 0.5)}

    got = weights.make_init(abstract, Kind)(weights.seed_key(3))
    assert got["blocks"][0]["gate_bias"].dtype == jnp.bfloat16
    assert (got["blocks"][0]["gate_bias"] == 0.5).all()
    with pytest.raises(ValueError, match="gate_bias"):
        weights.make_init(abstract, object())(weights.seed_key(3))


# ---------------------------------------------------------------- pinned

# flops.train_step at each cell's traffic size, as counted before the
# counts moved into the kinds; mfu.train reads these.
TRAIN_WORK = {("mamba2-1.3b-l12", 2, 4096): (21092751900672.0,
                                             31894179840.0),
              ("granite-moe-1b-a400m-l6", 8, 1024): (7440376971264.0,
                                                     14712471552.0)}


@pytest.mark.parametrize("name,B,S", list(TRAIN_WORK))
def test_the_train_work_of_each_configuration_is_pinned(name, B, S):
    kind = kinds.kind(spec.config_file(name))
    w = flops.train_step(kind, spec.arch_config(name), B, S)
    assert (w.flops, w.bytes) == TRAIN_WORK[name, B, S]


# sha256 of the small-size weights from seed 2**40 + 7 (leaf paths, dtypes,
# shapes and bytes), as drawn before the kinds.
WEIGHTS_DIGEST = {
    "mamba2-1.3b-l12":
        "91dec8a3c5108fabfa5a9220d5beea5be7ad047aeddbc6f2ed15e063fa3f7703",
    "granite-moe-1b-a400m-l6":
        "50619f95539cecf7d750f3ad420650e63b6c8d1690e69dd5766ea3ad8ee36c04",
}


@pytest.mark.parametrize("name", list(WEIGHTS_DIGEST))
def test_the_small_weights_of_each_configuration_are_pinned(name):
    cfg, f = small.configs(name)
    params = weights.make_init(weights.layout(cfg), kinds.kind(f))(
        weights.seed_key(2**40 + 7))
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        a = np.asarray(leaf)
        for part in (jax.tree_util.keystr(path), str(a.dtype), str(a.shape)):
            h.update(part.encode())
        h.update(a.tobytes())
    assert h.hexdigest() == WEIGHTS_DIGEST[name]
