"""Grouped expert GEMM Pallas kernel (MoE hot loop).

Computes out[e] = x[e] @ w[e] for every expert's capacity-dispatched token
block — the compute core of the MoE layer once the locality-aware router
(repro.core.routing) has packed tokens into (E, C, D).

TPU mapping: grid = (E, C/bc, F/bf, D/bd), f32 accumulator tile (bc × bf)
in VMEM carried over the inner D axis; every matmul is MXU-shaped
(bc, bd) × (bd, bf) with 128-aligned defaults. Experts ride the outermost
grid axis so each expert's weight tile streams HBM→VMEM exactly once per
(ci, fi) tile — the layout a GPU grouped-GEMM achieves with CTA swizzling
falls out of the grid order here.

Oracle: :func:`repro.kernels.ref.moe_gmm_ref`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["moe_gmm_kernel_call"]


def _kernel(x_ref, w_ref, o_ref, acc_ref):
    di = pl.program_id(3)

    @pl.when(di == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot(
        x_ref[...].astype(jnp.float32), w_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32)

    @pl.when(di == pl.num_programs(3) - 1)
    def _emit():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def moe_gmm_kernel_call(x: jnp.ndarray, w: jnp.ndarray,
                        block_c: int = 128, block_f: int = 128,
                        block_d: int = 128,
                        interpret: bool = False) -> jnp.ndarray:
    """x: (E, C, D) dispatched tokens; w: (E, D, F). Returns (E, C, F)."""
    E, C, D = x.shape
    _, _, F = w.shape
    block_c = min(block_c, C)
    block_f = min(block_f, F)
    block_d = min(block_d, D)
    for name, dim, blk in (("C", C, block_c), ("F", F, block_f),
                           ("D", D, block_d)):
        if dim % blk:
            raise ValueError(f"{name}={dim} not divisible by block {blk}")
    grid = (E, C // block_c, F // block_f, D // block_d)
    return pl.pallas_call(
        _kernel,
        name="moe_gmm",
        out_shape=jax.ShapeDtypeStruct((E, C, F), x.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_c, block_d),
                         lambda e, ci, fi, di: (e, ci, di)),
            pl.BlockSpec((None, block_d, block_f),
                         lambda e, ci, fi, di: (e, di, fi)),
        ],
        out_specs=pl.BlockSpec((None, block_c, block_f),
                               lambda e, ci, fi, di: (e, ci, fi)),
        scratch_shapes=[pltpu.VMEM((block_c, block_f), jnp.float32)],
        interpret=interpret,
    )(x, w)
