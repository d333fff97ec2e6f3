"""Model facade: init / train loss / forward / prefill / decode.

One implementation covers all ten assigned architectures via the config's
layer pattern (see stack.py). Modality frontends are stubs per the
assignment: audio passes precomputed frame embeddings, VLM passes
precomputed patch embeddings as cross-attention media.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from . import layers, stack

Params = dict[str, Any]


def init_params(cfg, key) -> Params:
    k_emb, k_stack, k_head = jax.random.split(key, 3)
    D, V = cfg.d_model, cfg.vocab_size
    p: Params = {
        "blocks": stack.init_stack(k_stack, cfg),
        "final_norm": layers.rms_weight(D, cfg.param_dtype),
    }
    if not cfg.embeds_input:
        p["embed"] = (jax.random.normal(k_emb, (V, D)) * 0.02
                      ).astype(cfg.param_dtype)
    if cfg.tie_embeddings and not cfg.embeds_input:
        pass  # reuse p["embed"].T at the head
    else:
        p["lm_head"] = (jax.random.normal(k_head, (D, V)) / np.sqrt(D)
                        ).astype(cfg.param_dtype)
    return p


def abstract_params(cfg, dtype_override=None):
    """ShapeDtypeStruct tree without allocating (dry-run path)."""
    out = jax.eval_shape(lambda k: init_params(cfg, k),
                         jax.random.PRNGKey(0))
    if dtype_override is not None:
        out = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, dtype_override)
            if jnp.issubdtype(s.dtype, jnp.floating) else s, out)
    return out


@jax.named_scope("embed")
def _embed(params, cfg, tokens=None, embeds=None):
    if cfg.embeds_input:
        assert embeds is not None, "this arch takes frontend embeddings"
        return embeds.astype(cfg.param_dtype)
    return params["embed"][tokens]


@jax.named_scope("head")
def _head(params, cfg, x):
    x = layers.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    if cfg.tie_embeddings and "lm_head" not in params:
        w = params["embed"].T
    else:
        w = params["lm_head"]
    return (x @ w).astype(jnp.float32)


def forward(params, cfg, tokens=None, embeds=None, media=None,
            steal_table=None):
    """Full-sequence logits (training teacher-forcing / encoder forward).

    Returns (logits, aux_loss)."""
    x = _embed(params, cfg, tokens, embeds)
    B, S = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    x, _, aux = stack.apply_stack(params["blocks"], cfg, x,
                                  positions=positions, media=media,
                                  steal_table=steal_table, mode="train")
    return _head(params, cfg, x), aux


def train_loss(params, cfg, batch, steal_table=None):
    """Cross-entropy (+ router aux + z-loss). batch: dict with
    tokens/embeds, labels (B, S) int32 (-100 = masked), optional media."""
    logits, aux = forward(params, cfg, tokens=batch.get("tokens"),
                          embeds=batch.get("embeds"),
                          media=batch.get("media"),
                          steal_table=steal_table)
    with jax.named_scope("head"):
        labels = batch["labels"]
        valid = labels >= 0
        labels_safe = jnp.where(valid, labels, 0)
        logp = jax.nn.log_softmax(logits, axis=-1)
        ll = jnp.take_along_axis(logp, labels_safe[..., None], -1)[..., 0]
        denom = jnp.maximum(valid.sum(), 1)
        ce = -(ll * valid).sum() / denom
        # z-loss stabilizes the softmax normalizer at scale
        zl = jnp.square(jax.nn.logsumexp(logits, axis=-1))
        z_loss = (zl * valid).sum() / denom
        loss = ce + cfg.router_aux_weight * aux + cfg.z_loss_weight * z_loss
    return loss, dict(ce=ce, aux=aux, z_loss=z_loss)


def prefill(params, cfg, tokens=None, embeds=None, media=None,
            max_len: int | None = None, steal_table=None):
    """Process a prompt, returning (last_logits, caches)."""
    x = _embed(params, cfg, tokens, embeds)
    B, S = x.shape[:2]
    max_len = max_len or S
    caches = stack.init_caches(cfg, B, max_len, cfg.param_dtype)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    x, caches, _ = stack.apply_stack(params["blocks"], cfg, x,
                                     positions=positions, media=media,
                                     caches=caches, mode="prefill",
                                     steal_table=steal_table)
    return _head(params, cfg, x[:, -1:]), caches


def decode_step(params, cfg, caches, tokens, steal_table=None):
    """One decode step. tokens: (B, 1) int32. Returns (logits, caches)."""
    x = _embed(params, cfg, tokens)
    B = x.shape[0]
    pos = jnp.broadcast_to(caches["length"], (B, 1)).astype(jnp.int32)
    x, caches, _ = stack.apply_stack(params["blocks"], cfg, x,
                                     positions=pos, caches=caches,
                                     mode="decode", steal_table=steal_table)
    return _head(params, cfg, x), caches


def param_count(cfg) -> int:
    tree = abstract_params(cfg)
    return sum(int(np.prod(s.shape)) for s in jax.tree.leaves(tree))


def active_param_count(cfg) -> int:
    """Params touched per token (MoE: top_k of num_experts)."""
    total = param_count(cfg)
    if cfg.moe_num_experts == 0:
        return total
    tree = abstract_params(cfg)
    expert = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        keys = [getattr(p, "key", getattr(p, "name", "")) for p in path]
        if "ffn" in keys and any(k in ("wg", "wu", "wd") for k in keys):
            # stacked expert weights (R, slots..., E, D, F)
            if len(leaf.shape) >= 3 and leaf.shape[-3] == cfg.moe_num_experts:
                expert += int(np.prod(leaf.shape))
    active = total - expert + expert * cfg.moe_top_k // cfg.moe_num_experts
    return active
