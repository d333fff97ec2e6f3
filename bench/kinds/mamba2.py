"""Mamba2 (``model_type: mamba2``, or none): every layer is a Mamba2 mixer
(in projection, causal depthwise conv with SiLU, the SSD recurrence, gated
RMSNorm, out projection) with no MLP; the head is the tied embedding.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from bench import flops
from bench.reference.model import Spec, mamba, rmsnorm, tied_head

WIDTHS = [("d_model", "d_model"), ("d_state", "ssm_state"),
          ("d_conv", "ssm_conv"), ("expand", "ssm_expand"),
          ("headdim", "ssm_head_dim"), ("ngroups", "ssm_groups")]


def overrides(f: dict) -> dict:
    return dict(num_layers=f["n_layer"], vocab_size=f["vocab_size"],
                ssm_chunk=f["chunk_size"], norm_eps=f["norm_eps"],
                tie_embeddings=f["tie_embeddings"], dtype=f["dtype"])


# ---------------------------------------------------------------- reference

def spec_from_file(f: dict) -> Spec:
    d_inner = f["expand"] * f["d_model"]
    return Spec(layers=f["n_layer"], D=f["d_model"],
                V=f["vocab_size"], eps=f["norm_eps"], d_inner=d_inner,
                N=f["d_state"], P=f["headdim"], G=f["ngroups"],
                conv=f["d_conv"],
                aux_weight=f["program"]["router_aux_weight"],
                z_weight=f["program"]["z_loss_weight"])


def layer(x, p, spec: Spec, num, prompt_len: int, table):
    """One layer on the residual stream x (B, L, D) f32 -> (x, aux)."""
    p = jax.tree.map(lambda t: t.astype(jnp.float32), p)
    return x + mamba(rmsnorm(x, p["ln1"], spec.eps), p["mix"], spec,
                     num), jnp.zeros(())


def slots(spec: Spec):
    return (layer,)


head = tied_head


def train_table(spec: Spec):
    return None


serve_table = train_table


# ---------------------------------------------------------------- work

def work(cfg, B, S, start, carried) -> flops.Work:
    T, D = B * S, cfg.d_model
    d_inner = cfg.ssm_expand * D
    layer = flops.rmsnorm(T, D) + flops.mamba_mixer(
        B, S, D, d_inner, d_inner // cfg.ssm_head_dim, cfg.ssm_state,
        cfg.ssm_head_dim, cfg.ssm_groups, cfg.ssm_conv, carried)
    return layer * cfg.num_layers


# ---------------------------------------------------------------- small

def small(f: dict, base):
    f.update(d_model=base.d_model, n_layer=2, d_state=base.ssm_state,
             headdim=base.ssm_head_dim, vocab_size=base.vocab_size,
             chunk_size=8, dtype="float32")
    cfg = dataclasses.replace(base, num_layers=2, ssm_chunk=8,
                              norm_eps=f["norm_eps"], remat="none")
    return cfg, f
