"""Granite MoE (``model_type: granitemoe``): every layer is GQA attention
with RoPE, then a mixture of SwiGLU experts behind a softmax top-k router;
the head is the tied embedding.

The reference routes with the program's capacity and steals, from the
file's ``program`` group: the nearest-victim (torus) order in training,
and in serving the ring order the router walks when given no table.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from bench import flops
from bench.reference.model import (Spec, attention, moe, ring_table, rmsnorm,
                                   tied_head, torus_table)

WIDTHS = [("hidden_size", "d_model"), ("intermediate_size", "moe_d_ff"),
          ("num_attention_heads", "num_heads"),
          ("num_key_value_heads", "num_kv_heads"),
          ("num_local_experts", "moe_num_experts"),
          ("num_experts_per_tok", "moe_top_k")]


def overrides(f: dict) -> dict:
    return dict(num_layers=f["num_hidden_layers"],
                vocab_size=f["vocab_size"], rope_theta=f["rope_theta"],
                norm_eps=f["rms_norm_eps"],
                tie_embeddings=f["tie_word_embeddings"],
                dtype=f["torch_dtype"])


# ---------------------------------------------------------------- reference

def spec_from_file(f: dict) -> Spec:
    pr = f["program"]
    return Spec(layers=f["num_hidden_layers"],
                D=f["hidden_size"], V=f["vocab_size"],
                eps=f["rms_norm_eps"], H=f["num_attention_heads"],
                Hkv=f["num_key_value_heads"],
                Dh=f["hidden_size"] // f["num_attention_heads"],
                theta=f["rope_theta"], E=f["num_local_experts"],
                K=f["num_experts_per_tok"], F=f["intermediate_size"],
                capacity_factor=pr["capacity_factor"],
                group=pr["moe_group"],
                steal_attempts=pr["moe_steal_attempts"],
                aux_weight=pr["router_aux_weight"],
                z_weight=pr["z_loss_weight"])


def layer(x, p, spec: Spec, num, prompt_len: int, table):
    """One layer on the residual stream x (B, L, D) f32 -> (x, aux)."""
    p = jax.tree.map(lambda t: t.astype(jnp.float32), p)
    x = x + attention(rmsnorm(x, p["ln1"], spec.eps), p["mix"], spec, num)
    y, aux = moe(rmsnorm(x, p["ln2"], spec.eps), p["ffn"], spec, num,
                 prompt_len, table)
    return x + y, aux


def slots(spec: Spec):
    return (layer,)


head = tied_head


def train_table(spec: Spec):
    return torus_table(spec.E)


def serve_table(spec: Spec):
    return ring_table(spec.E)


# ---------------------------------------------------------------- work

def work(cfg, B, S, start, carried) -> flops.Work:
    T, D = B * S, cfg.d_model
    layer = (flops.rmsnorm(T, D)
             + flops.attention(B, S, start, D, cfg.num_heads,
                               cfg.num_kv_heads, cfg.head_dim)
             + flops.rmsnorm(T, D)
             + flops.moe_experts(T, D, cfg.moe_d_ff, cfg.moe_num_experts,
                                 cfg.moe_top_k))
    return layer * cfg.num_layers


# ---------------------------------------------------------------- small

def small(f: dict, base):
    f.update(hidden_size=base.d_model, intermediate_size=base.moe_d_ff,
             num_hidden_layers=2, num_attention_heads=base.num_heads,
             num_key_value_heads=base.num_kv_heads,
             num_local_experts=base.moe_num_experts,
             num_experts_per_tok=base.moe_top_k,
             vocab_size=base.vocab_size, torch_dtype="float32")
    f["program"] = dict(f["program"], moe_group=64)
    cfg = dataclasses.replace(
        base, num_layers=2, rope_theta=f["rope_theta"],
        norm_eps=f["rms_norm_eps"], **f["program"])
    return cfg, f
