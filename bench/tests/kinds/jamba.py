"""A model kind that comes only as a file: the program's
``jamba-1.5-large-398b`` as the program lays it out, for the tests of the
kind lookup (``bench/tests/test_kinds.py`` puts this directory on the
lookup's search path).

A period of 8 slots: attention in slot ``attn_layer_offset``, Mamba2
mixers in the others; a mixture of experts in the slots ``i`` with
``i % expert_layer_period == expert_layer_offset`` and a plain SwiGLU MLP
in the rest; an untied ``lm_head``. The MLP's reference and its count are
this file's own; the rest are the shared primitives.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from bench import flops
from bench.reference.model import (Spec, attention, mamba, moe, rmsnorm,
                                   torus_table, untied_head)

WIDTHS = [("hidden_size", "d_model"), ("intermediate_size", "d_ff"),
          ("moe_intermediate_size", "moe_d_ff"),
          ("num_attention_heads", "num_heads"),
          ("num_key_value_heads", "num_kv_heads"),
          ("num_experts", "moe_num_experts"),
          ("num_experts_per_tok", "moe_top_k"),
          ("mamba_d_state", "ssm_state"), ("mamba_d_conv", "ssm_conv"),
          ("mamba_expand", "ssm_expand"), ("mamba_headdim", "ssm_head_dim"),
          ("mamba_ngroups", "ssm_groups")]


def overrides(f: dict) -> dict:
    return dict(num_layers=f["num_hidden_layers"],
                vocab_size=f["vocab_size"], rope_theta=f["rope_theta"],
                norm_eps=f["rms_norm_eps"], ssm_chunk=f["mamba_chunk_size"],
                tie_embeddings=f["tie_word_embeddings"],
                dtype=f["torch_dtype"])


# ---------------------------------------------------------------- reference

@dataclasses.dataclass(frozen=True)
class JambaSpec(Spec):
    attn_period: int = 0
    attn_offset: int = 0
    expert_period: int = 0
    expert_offset: int = 0


def spec_from_file(f: dict) -> JambaSpec:
    pr = f["program"]
    D, H, P = f["hidden_size"], f["num_attention_heads"], f["mamba_headdim"]
    d_inner = f["mamba_expand"] * D
    return JambaSpec(
        layers=f["num_hidden_layers"], D=D, V=f["vocab_size"],
        eps=f["rms_norm_eps"], H=H, Hkv=f["num_key_value_heads"], Dh=D // H,
        theta=f["rope_theta"], E=f["num_experts"],
        K=f["num_experts_per_tok"], F=f["moe_intermediate_size"],
        capacity_factor=pr["capacity_factor"], group=pr["moe_group"],
        steal_attempts=pr["moe_steal_attempts"], d_inner=d_inner,
        N=f["mamba_d_state"], P=P, G=f["mamba_ngroups"],
        conv=f["mamba_d_conv"], aux_weight=pr["router_aux_weight"],
        z_weight=pr["z_loss_weight"], attn_period=f["attn_layer_period"],
        attn_offset=f["attn_layer_offset"],
        expert_period=f["expert_layer_period"],
        expert_offset=f["expert_layer_offset"])


def mlp(h, p, num):
    """SwiGLU: (silu(h wg) * (h wu)) wd."""
    g = num.ein("bld,df->blf", h, p["wg"])
    u = num.ein("bld,df->blf", h, p["wu"])
    return num.ein("blf,fd->bld", jax.nn.silu(g) * u, p["wd"])


def _slot(mixer: str, ffn: str):
    def slot(x, p, spec, num, prompt_len, table):
        p = jax.tree.map(lambda t: t.astype(jnp.float32), p)
        h = rmsnorm(x, p["ln1"], spec.eps)
        x = x + (attention(h, p["mix"], spec, num) if mixer == "attn"
                 else mamba(h, p["mix"], spec, num))
        h = rmsnorm(x, p["ln2"], spec.eps)
        if ffn == "moe":
            y, aux = moe(h, p["ffn"], spec, num, prompt_len, table)
        else:
            y, aux = mlp(h, p["ffn"], num), jnp.zeros(())
        return x + y, aux

    return slot


def period(spec: JambaSpec) -> list[tuple[str, str]]:
    """(mixer, ffn) of each slot."""
    return [("attn" if i == spec.attn_offset else "mamba",
             "moe" if i % spec.expert_period == spec.expert_offset
             else "mlp") for i in range(spec.attn_period)]


def slots(spec: JambaSpec):
    return tuple(_slot(m, f) for m, f in period(spec))


head = untied_head


def train_table(spec: JambaSpec):
    return torus_table(spec.E)


serve_table = train_table


# ---------------------------------------------------------------- work

def mlp_work(T, D, F) -> flops.Work:
    """A SwiGLU MLP for T tokens."""
    return flops.Work(2 * T * 3 * D * F, 3 * D * F * flops.W
                      + 2 * T * D * flops.W)


def work(cfg, B, S, start, carried) -> flops.Work:
    T, D = B * S, cfg.d_model
    d_inner = cfg.ssm_expand * D
    w = flops.Work()
    for mixer, ffn in cfg.pattern:
        if mixer == "attn":
            mix = flops.attention(B, S, start, D, cfg.num_heads,
                                  cfg.num_kv_heads, cfg.head_dim)
        else:
            mix = flops.mamba_mixer(
                B, S, D, d_inner, d_inner // cfg.ssm_head_dim, cfg.ssm_state,
                cfg.ssm_head_dim, cfg.ssm_groups, cfg.ssm_conv, carried)
        ff = (flops.moe_experts(T, D, cfg.moe_d_ff, cfg.moe_num_experts,
                                cfg.moe_top_k) if ffn == "moe"
              else mlp_work(T, D, cfg.d_ff))
        w += flops.rmsnorm(T, D) + mix + flops.rmsnorm(T, D) + ff
    return w * cfg.repeats


# ---------------------------------------------------------------- small

def small(f: dict, base):
    f.update(hidden_size=base.d_model, intermediate_size=base.d_ff,
             moe_intermediate_size=base.moe_d_ff,
             num_hidden_layers=base.num_layers,
             num_attention_heads=base.num_heads,
             num_key_value_heads=base.num_kv_heads,
             num_experts=base.moe_num_experts,
             num_experts_per_tok=base.moe_top_k,
             mamba_d_state=base.ssm_state, mamba_headdim=base.ssm_head_dim,
             mamba_ngroups=base.ssm_groups,
             mamba_chunk_size=base.ssm_chunk, vocab_size=base.vocab_size,
             torch_dtype="float32")
    f["program"] = dict(f["program"], moe_group=base.moe_group)
    cfg = dataclasses.replace(base, rope_theta=f["rope_theta"],
                              norm_eps=f["rms_norm_eps"], **f["program"])
    return cfg, f
