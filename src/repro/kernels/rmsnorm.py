"""Fused RMSNorm Pallas kernel.

One pass over rows resident in VMEM: mean-square, rsqrt, scale — no
intermediate HBM round-trips (XLA typically fuses this too; the kernel
exists to pin the layout and as the simplest template of the package's
kernel/ops/ref pattern).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["rmsnorm_kernel_call"]


def _kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(ms + eps)
                  * w_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def rmsnorm_kernel_call(x: jnp.ndarray, w: jnp.ndarray,
                        eps: float = 1e-6,
                        block_rows: int = 256,
                        interpret: bool = False) -> jnp.ndarray:
    """x: (N, D) — callers flatten leading dims; w: (D,)."""
    n, d = x.shape
    block_rows = min(block_rows, n)
    if n % block_rows:
        raise ValueError(f"rows {n} not divisible by block_rows {block_rows}")
    return pl.pallas_call(
        functools.partial(_kernel, eps=eps),
        name="rmsnorm",
        out_shape=jax.ShapeDtypeStruct((n, d), x.dtype),
        grid=(n // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
                  pl.BlockSpec((d,), lambda i: (0,))],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        interpret=interpret,
    )(x, w)
