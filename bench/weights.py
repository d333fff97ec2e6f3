"""Weights drawn from the seed on the device, in one jitted call.

The benchmark makes the weights itself, so that the reference can make
them again from the seed and take nothing the program has made. Only the
tree's layout (names, shapes, dtypes) is taken from the program, from
``jax.eval_shape`` of its own init; every value is drawn here by the rule
for the leaf's name: the rules here, and those a model kind adds for
names of its own (``WEIGHTS`` of its module in ``bench/kinds/``). A leaf
name without a rule is an error, so a layout change in the program cannot
pass unnoticed.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

_NORMS = {"ln1", "ln2", "final_norm", "out_norm", "q_norm", "k_norm"}
_MATRICES = {"wq", "wk", "wv", "wo", "wg", "wu", "wd", "in_proj",
             "out_proj", "router", "lm_head"}


def seed_key(seed: int):
    """A PRNG key for any whole-number seed (also beyond 32 bits)."""
    s = int(seed) & ((1 << 64) - 1)
    k = jax.random.PRNGKey(0)
    k = jax.random.fold_in(k, np.uint32(s & 0xFFFFFFFF))
    return jax.random.fold_in(k, np.uint32(s >> 32))


def _leaf(name: str, key, shape, dtype, rules: dict):
    n = jax.random.normal(key, shape, jnp.float32)
    if name == "embed":
        v = n * 0.02
    elif name in _NORMS:
        v = 1.0 + 0.05 * n
    elif name in _MATRICES:
        v = n / np.sqrt(shape[-2])            # fan-in of the last two axes
    elif name == "conv_w":
        v = n * 0.5                            # ~ U(+-1/sqrt(d_conv)) scale
    elif name == "conv_b":
        v = n * 0.05
    elif name == "A_log":                      # A ~ U(1, 16), as Mamba2
        v = jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    elif name == "dt_bias":                    # dt ~ logU(1e-3, 1e-1)
        u = jax.random.uniform(key, shape, jnp.float32)
        dt = jnp.exp(u * (np.log(0.1) - np.log(1e-3)) + np.log(1e-3))
        v = dt + jnp.log(-jnp.expm1(-dt))      # inverse softplus
    elif name == "D_skip":
        v = 1.0 + 0.1 * n
    elif name in rules:
        v = rules[name](key, shape)
    else:
        raise ValueError(f"no rule to draw weight leaf {name!r}")
    return v.astype(dtype)


def _name(path) -> str:
    return str(getattr(path[-1], "key", getattr(path[-1], "idx", "")))


def layout(cfg):
    """The program's parameter tree as shapes (no values)."""
    from repro.models import model as model_lib

    return jax.eval_shape(lambda k: model_lib.init_params(cfg, k),
                          jax.random.PRNGKey(0))


def make_init(abstract, kind):
    """A jitted ``init(key) -> params`` for the tree ``abstract`` of a model
    of ``kind``."""
    rules = getattr(kind, "WEIGHTS", {})
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)

    @jax.jit
    def init(key):
        keys = jax.random.split(key, len(flat))
        leaves = [_leaf(_name(p), k, s.shape, s.dtype, rules)
                  for (p, s), k in zip(flat, keys)]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return init
