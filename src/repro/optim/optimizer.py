"""Optimizer substrate: AdamW + schedules + grad accumulation +
int8 gradient compression with error feedback.

All states are pytrees shaped like the params, so the sharding rules
engine shards optimizer state exactly like the parameters (ZeRO-style:
params/м/v sharded over the data axis — GSPMD materializes gathers).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

Params = Any

__all__ = ["AdamWConfig", "adamw_init", "adamw_update", "cosine_schedule",
           "global_norm", "clip_by_global_norm", "accumulate_gradients",
           "compress_int8", "decompress_int8", "CompressionState",
           "compressed_gradients"]


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    lr_peak: float = 3e-4
    warmup_steps: int = 200
    total_steps: int = 10000
    lr_min_ratio: float = 0.1
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0
    # memory mode for ≥100B models on 16 GB/chip: Adafactor-style
    # factored second moment (row/col stats) + bf16 first moment.
    factored: bool = False
    m_dtype: str = "float32"


def cosine_schedule(cfg: AdamWConfig, step):
    step = jnp.asarray(step, jnp.float32)
    warm = jnp.minimum(step / jnp.maximum(cfg.warmup_steps, 1), 1.0)
    prog = jnp.clip((step - cfg.warmup_steps)
                    / jnp.maximum(cfg.total_steps - cfg.warmup_steps, 1),
                    0.0, 1.0)
    cos = 0.5 * (1 + jnp.cos(jnp.pi * prog))
    frac = cfg.lr_min_ratio + (1 - cfg.lr_min_ratio) * cos
    return cfg.lr_peak * warm * frac


def adamw_init(params: Params, cfg: AdamWConfig | None = None) -> dict:
    cfg = cfg or AdamWConfig()
    m_dt = jnp.dtype(cfg.m_dtype)

    def v_init(p):
        if cfg.factored and p.ndim >= 2:
            return dict(vr=jnp.zeros(p.shape[:-1], jnp.float32),
                        vc=jnp.zeros(p.shape[:-2] + p.shape[-1:],
                                     jnp.float32))
        return jnp.zeros(p.shape, jnp.float32)

    return dict(
        m=jax.tree.map(lambda p: jnp.zeros(p.shape, m_dt), params),
        v=jax.tree.map(v_init, params),
        count=jnp.zeros((), jnp.int32),
    )


def global_norm(tree: Params):
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree.leaves(tree)))


def clip_by_global_norm(grads: Params, max_norm: float):
    g = global_norm(grads)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(g, 1e-9))
    return jax.tree.map(lambda x: (x.astype(jnp.float32) * scale)
                        .astype(x.dtype), grads), g


@jax.named_scope("optimizer")
def adamw_update(grads: Params, state: dict, params: Params,
                 cfg: AdamWConfig):
    """One AdamW step. Returns (new_params, new_state, metrics)."""
    grads, gnorm = clip_by_global_norm(grads, cfg.clip_norm)
    count = state["count"] + 1
    lr = cosine_schedule(cfg, count)
    b1c = 1 - cfg.b1 ** count.astype(jnp.float32)
    b2c = 1 - cfg.b2 ** count.astype(jnp.float32)

    def upd(p, g, m, v):
        gf = g.astype(jnp.float32)
        m_new = cfg.b1 * m.astype(jnp.float32) + (1 - cfg.b1) * gf
        if isinstance(v, dict):
            # Adafactor-style factored second moment
            g2 = gf * gf + 1e-30
            vr = cfg.b2 * v["vr"] + (1 - cfg.b2) * g2.mean(-1)
            vc = cfg.b2 * v["vc"] + (1 - cfg.b2) * g2.mean(-2)
            vh = (vr[..., :, None] * vc[..., None, :]
                  / jnp.maximum(vr.mean(-1)[..., None, None], 1e-30)) / b2c
            v_new = dict(vr=vr, vc=vc)
        else:
            v_new = cfg.b2 * v + (1 - cfg.b2) * gf * gf
            vh = v_new / b2c
        mh = m_new / b1c
        step = mh / (jnp.sqrt(vh) + cfg.eps)
        pf = p.astype(jnp.float32)
        # decoupled weight decay on matrices only (ndim ≥ 2)
        if p.ndim >= 2:
            step = step + cfg.weight_decay * pf
        return (pf - lr * step).astype(p.dtype), m_new.astype(m.dtype), v_new

    is_v_leaf = lambda x: isinstance(x, dict) and "vr" in x
    flat_p, tdef = jax.tree.flatten(params)
    flat_g = jax.tree.leaves(grads)
    flat_m = jax.tree.leaves(state["m"])
    flat_v = jax.tree.flatten(state["v"], is_leaf=is_v_leaf)[0]

    # Chain the big-leaf updates with optimization barriers so the
    # scheduler can't run every leaf's f32 transients concurrently —
    # otherwise peak temp memory scales with the whole parameter tree
    # instead of one leaf (elementwise updates gain nothing from overlap).
    big = 1 << 25  # 32M elements
    out = []
    prev_done = None
    for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v):
        if prev_done is not None and p.size >= big:
            p, prev_done = jax.lax.optimization_barrier((p, prev_done))
        res = upd(p, g, m, v)
        if p.size >= big:
            prev_done = res[0]
        out.append(res)
    new_p = tdef.unflatten([o[0] for o in out])
    new_m = tdef.unflatten([o[1] for o in out])
    new_v = tdef.unflatten([o[2] for o in out])
    return new_p, dict(m=new_m, v=new_v, count=count), \
        dict(lr=lr, grad_norm=gnorm)


def accumulate_gradients(loss_fn: Callable, params: Params, batch: dict,
                         num_microbatches: int,
                         acc_dtype=None):
    """Grad accumulation via lax.scan over microbatch slices.

    loss_fn(params, microbatch) -> (loss, metrics). The global batch's
    leading axis is split into ``num_microbatches`` slices; returns mean
    loss/grads. One traced microbatch keeps the HLO small and caps
    activation memory at (batch / n_micro).

    acc_dtype: dtype of the accumulation buffer (default f32). bf16
    halves the second gradient-sized buffer on ≥100B models; the per-
    microbatch gradients are still produced in their natural dtype and
    summed into the buffer (loss scale 1/n applied at the end).
    """
    if num_microbatches <= 1:
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch)
        return loss, grads, metrics
    acc_dtype = jnp.dtype(acc_dtype or jnp.float32)

    def slice_mb(i):
        def f(x):
            mb = x.shape[0] // num_microbatches
            return jax.lax.dynamic_slice_in_dim(x, i * mb, mb, axis=0)
        return jax.tree.map(f, batch)

    def body(carry, i):
        loss_acc, grads_acc = carry
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, slice_mb(i))
        grads_acc = jax.tree.map(
            lambda a, g: (a + g.astype(acc_dtype)).astype(acc_dtype),
            grads_acc, grads)
        return (loss_acc + loss, grads_acc), metrics

    zero_grads = jax.tree.map(lambda p: jnp.zeros(p.shape, acc_dtype),
                              params)
    (loss_sum, grads_sum), metrics = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), zero_grads),
        jnp.arange(num_microbatches))
    n = float(num_microbatches)
    grads = jax.tree.map(lambda g: (g.astype(jnp.float32) / n), grads_sum)
    metrics = jax.tree.map(lambda m: m[-1], metrics)
    return loss_sum / n, grads, metrics


# ----------------------------------------------------------------------
# int8 gradient compression with error feedback (cross-pod reduction)
# ----------------------------------------------------------------------

@dataclasses.dataclass
class CompressionState:
    """Per-leaf error-feedback residuals (pytree like params)."""
    residual: Params


def compress_int8(x: jnp.ndarray):
    """Symmetric per-tensor int8 quantization. Returns (q, scale)."""
    amax = jnp.max(jnp.abs(x)).astype(jnp.float32)
    scale = jnp.maximum(amax / 127.0, 1e-12)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127
                 ).astype(jnp.int8)
    return q, scale


def decompress_int8(q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    return q.astype(jnp.float32) * scale


def compressed_gradients(grads: Params, comp: CompressionState | None):
    """Quantize grads to int8 with error feedback.

    The caller reduces the int8 payload across the slow (pod) axis —
    4× less DCI traffic than f32, 2× less than bf16 — then dequantizes.
    Error feedback carries the quantization residual into the next step,
    preserving convergence (1-bit-Adam-style analysis applies).

    Returns (dequantized_grads, new_comp_state) — in-graph simulation of
    the wire format so tests validate end-to-end numerics.
    """
    if comp is None:
        comp = CompressionState(residual=jax.tree.map(
            lambda g: jnp.zeros(g.shape, jnp.float32), grads))

    def one(g, r):
        gf = g.astype(jnp.float32) + r
        q, s = compress_int8(gf)
        deq = decompress_int8(q, s)
        return deq.astype(g.dtype), gf - deq

    flat_g, tdef = jax.tree.flatten(grads)
    flat_r = jax.tree.leaves(comp.residual)
    outs = [one(g, r) for g, r in zip(flat_g, flat_r)]
    return tdef.unflatten([o[0] for o in outs]), \
        CompressionState(tdef.unflatten([o[1] for o in outs]))
