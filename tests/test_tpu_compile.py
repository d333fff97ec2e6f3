"""Compile the main path for a described TPU v5e chip (no chip attached).

The TPU compiler refuses what interpret mode accepts: blocks that break
the (8, 128) tiling rule, primitives Mosaic cannot lower, programs that
overflow the device. These tests compile each Pallas kernel at the widths
``chip_smoke.py`` runs, plus granite-moe-1b-a400m's full-width prefill.
Nothing runs, so they say nothing about results or times.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.kernels import ops
from repro.models import model

V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


KERNELS = {
    # granite-moe-1b-a400m attention: Hq 16, Hkv 8, D 64
    "flash_attention": (
        lambda q, k, v: ops.flash_attention(q, k, v, causal=True,
                                            interpret=False),
        [(2, 1024, 16, 64), (2, 1024, 8, 64), (2, 1024, 8, 64)]),
    # granite experts: E 32, capacity 1280 (group 4096, top-8, x1.25)
    "moe_gmm": (
        lambda x, w: ops.moe_gmm(x, w, interpret=False),
        [(32, 1280, 1024), (32, 1024, 512)]),
    "rmsnorm": (
        lambda x, w: ops.rmsnorm(x, w, interpret=False),
        [(8192, 1024), (1024,)]),
    # mamba2-1.3b: H 64, P 64, G 1, N 128
    "ssd_scan": (
        lambda x, a, b, c: ops.ssd_scan(x, a, b, c, interpret=False),
        [(1, 2048, 64, 64), (1, 2048, 64), (1, 2048, 1, 128),
         (1, 2048, 1, 128)]),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = KERNELS[name]
    args = [_sds(one_chip, s) for s in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_granite_prefill_fits_one_v5e(one_chip):
    cfg = configs.get("granite-moe-1b-a400m")
    params = jax.tree.map(
        lambda s: _sds(one_chip, s.shape, s.dtype), model.abstract_params(cfg))
    tokens = _sds(one_chip, (8, 512), jnp.int32)
    compiled = jax.jit(
        lambda p, t: model.prefill(p, cfg, tokens=t, max_len=544)
    ).lower(params, tokens).compile()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        + mem.output_size_in_bytes
    assert used < V5E_HBM_BYTES
