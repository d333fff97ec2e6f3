"""Blocked (FlashAttention-style) attention Pallas kernel for TPU.

TPU-native design, not a CUDA port:
  * heads-major layout (B, H, S, D): every block's last two dims are
    (block, D), which meets the TPU's (8, 128) tiling rule — a BSHD
    block would squeeze the head axis into the second-minor position.
    ``ops.flash_attention`` transposes from the public BSHD layout.
  * grid = (B, Hq, Sq/bq, Skv/bk) with the KV axis innermost — the TPU
    grid is executed sequentially over the minor axis, so the online
    softmax state (m, l, acc) lives in VMEM scratch and is carried
    across KV blocks without any inter-block synchronization primitive
    (no equivalent of CUDA shared-memory staging is needed).
  * block shapes default to (128, 128): MXU-aligned on both matmuls
    (q·kᵀ and p·v), and the f32 accumulator tile (bq × D) stays in VMEM.
  * GQA is handled in the BlockSpec index_map (kv head = hq // group) —
    no repeated K/V materialization in HBM.
  * causal masking compares absolute positions, so the same kernel does
    prefill (Sq == Skv), chunked prefill and decode (Sq == 1) via
    ``kv_offset``; fully-masked KV blocks skip their matmuls with
    ``pl.when`` (the TPU analogue of Flash2's early-exit).

Oracle: :func:`repro.kernels.ref.attention_ref`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention_kernel_call"]

_NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, causal: bool, window: int | None,
            kv_offset: int, block_q: int, block_k: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * block_q + kv_offset   # absolute q positions
    k_start = ki * block_k

    # Whole-block skip: for causal layouts, KV blocks strictly above the
    # diagonal contribute nothing — skip both matmuls.
    qpos = q_start + jax.lax.iota(jnp.int32, block_q)
    kpos = k_start + jax.lax.iota(jnp.int32, block_k)
    block_live = True
    if causal:
        block_live = k_start <= q_start + block_q - 1
    if window is not None:
        block_live = jnp.logical_and(
            block_live, k_start + block_k - 1 > q_start - window)

    @pl.when(block_live)
    def _body():
        q = q_ref[...].astype(jnp.float32) * scale
        k = k_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (bq, bk)
        mask = jnp.ones((block_q, block_k), bool)
        if causal:
            mask &= kpos[None, :] <= qpos[:, None]
        if window is not None:
            mask &= kpos[None, :] > qpos[:, None] - window
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + p.sum(axis=-1, keepdims=True)
        v = v_ref[...].astype(jnp.float32)
        acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot(p, v)
        m_ref[...] = m_new

    @pl.when(ki == pl.num_programs(3) - 1)
    def _finalize():
        l = l_ref[...]
        # fully-masked rows (decode warm-up) produce l == 0 → emit zeros.
        out = acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = out.astype(o_ref.dtype)


def flash_attention_kernel_call(q: jnp.ndarray, k: jnp.ndarray,
                                v: jnp.ndarray,
                                causal: bool = True,
                                scale: float | None = None,
                                window: int | None = None,
                                kv_offset: int = 0,
                                block_q: int = 128,
                                block_k: int = 128,
                                interpret: bool = False) -> jnp.ndarray:
    """q: (B, Hq, Sq, D); k, v: (B, Hkv, Skv, D). Returns (B, Hq, Sq, D)."""
    B, Hq, Sq, D = q.shape
    _, Hkv, Skv, _ = k.shape
    if Hq % Hkv:
        raise ValueError(f"Hq={Hq} % Hkv={Hkv} != 0")
    group = Hq // Hkv
    block_q = min(block_q, Sq)
    block_k = min(block_k, Skv)
    if Sq % block_q or Skv % block_k:
        raise ValueError(f"seq lens ({Sq},{Skv}) not divisible by blocks "
                         f"({block_q},{block_k})")
    scale = (D ** -0.5) if scale is None else scale

    grid = (B, Hq, Sq // block_q, Skv // block_k)
    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window,
        kv_offset=kv_offset, block_q=block_q, block_k=block_k)
    return pl.pallas_call(
        kernel,
        name="flash_attention",
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq, D), q.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, block_q, D),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((None, None, block_k, D),
                         lambda b, h, qi, ki: (b, h // group, ki, 0)),
            pl.BlockSpec((None, None, block_k, D),
                         lambda b, h, qi, ki: (b, h // group, ki, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, block_q, D),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max m
            pltpu.VMEM((block_q, 1), jnp.float32),   # running denom l
            pltpu.VMEM((block_q, D), jnp.float32),   # output accumulator
        ],
        interpret=interpret,
    )(q, k, v)
