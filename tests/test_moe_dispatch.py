"""The MoE layer moves rows between tokens and expert slots by index
gathers (``routing.slot_maps``, ``layers._dispatch``, ``layers._combine``).
Here it is held to the dense one-hot dispatch and combine it replaced,
which lives only in this file: the output and the gradients of the input,
the router and the expert weights, over drops at capacity, a token that a
steal places twice in one expert, several token groups, and both expert
paths. And its backward scatters no row of activations."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core.routing import RoutingConfig, route
from repro.models import layers

B, S, D, E, K, F = 2, 64, 64, 8, 2, 32

# name: (capacity_factor, steal attempts, moe_group)
CASES = {
    "drops": (0.5, 0, 128),
    "steal-twice": (1.0, 2, 128),
    "groups": (0.75, 2, 32),
}
TOL = {"float32": 1e-5, "bfloat16": 1e-2}


def _cfg(case, dtype, impl):
    cf, steals, group = CASES[case]
    base = configs.get("granite-moe-1b-a400m").reduced()
    return dataclasses.replace(
        base, d_model=D, moe_num_experts=E, moe_top_k=K, moe_d_ff=F,
        capacity_factor=cf, moe_steal_attempts=steals, moe_group=group,
        moe_impl=impl, dtype=dtype)


def _inputs(cfg):
    kx, kp = jax.random.split(jax.random.PRNGKey(7))
    p = layers.init_moe(kp, cfg)
    # a shared first feature pulls every token to experts 0, then 1: expert
    # 0 overflows, and the ring's first victim of 0 is 1
    p["router"] = p["router"].at[0, :2].add(jnp.array([6.0, 4.0]))
    x = jax.random.normal(kx, (B, S, D)).at[..., 0].add(1.0)
    return x.astype(cfg.param_dtype), p


def _capacity(cfg, G):
    return max(int(np.ceil(G * K * cfg.capacity_factor / E)), K)


def _routing(x, p, cfg):
    """The layer's routing, per group: (expert, slot, weight)."""
    G = min(cfg.moe_group, B * S)
    xg = x.reshape(-1, G, D)
    rcfg = RoutingConfig(E, K, _capacity(cfg, G), cfg.moe_steal_attempts)
    table = np.stack([(e + np.arange(1, E)) % E for e in range(E)])

    def one(x1):
        r = route(x1.astype(jnp.float32) @ p["router"], rcfg, table)
        return r["expert"], r["slot"], r["weight"]

    return jax.vmap(one)(xg)


def onehot_moe(x, p, cfg):
    """The dense GShard dispatch and combine over (g, s, E, C) one-hots."""
    G = min(cfg.moe_group, B * S)
    C = _capacity(cfg, G)
    xg = x.reshape(-1, G, D)
    expert, slot, weight = _routing(x, p, cfg)
    e_oh = jax.nn.one_hot(expert, E, dtype=xg.dtype)
    c_oh = jax.nn.one_hot(slot, C, dtype=xg.dtype)
    combine = jnp.einsum("gske,gskc,gsk->gsec", e_oh, c_oh,
                         weight.astype(xg.dtype))
    dispatch = jnp.einsum("gske,gskc->gsec", e_oh, c_oh)
    xin = jnp.einsum("gsec,gsd->gecd", dispatch, xg)
    h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", xin, p["wg"])) \
        * jnp.einsum("gecd,edf->gecf", xin, p["wu"])
    eout = jnp.einsum("gecf,efd->gecd", h, p["wd"])
    return jnp.einsum("gsec,gecd->gsd", combine, eout).reshape(B, S, D)


def _value_and_grads(fn, x, p):
    probe = jax.random.normal(jax.random.PRNGKey(3), (B, S, D))

    def loss(x, p):
        return jnp.sum(fn(x, p).astype(jnp.float32) * probe)

    y = jax.jit(fn)(x, p)
    gx, gp = jax.jit(jax.grad(loss, argnums=(0, 1)))(x, p)
    return {"y": y, "x": gx, **{k: gp[k] for k in ("router", "wg", "wu",
                                                   "wd")}}


def _rel(a, b):
    a, b = (np.asarray(v, np.float64) for v in (a, b))
    return np.linalg.norm(a - b) / np.linalg.norm(b)


@pytest.mark.parametrize("impl", ["einsum", "kernel"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_gathers_match_the_onehot_dispatch(case, dtype, impl):
    cfg = _cfg(case, dtype, impl)
    x, p = _inputs(cfg)
    expert, slot, _ = (np.asarray(a) for a in _routing(x, p, cfg))
    if case == "drops":
        assert (expert < 0).any(), "no pair was dropped"
    if case == "steal-twice":
        assert (expert[..., 0] == expert[..., 1]).any(), \
            "no token sits twice in one expert"
    if case == "groups":
        assert expert.shape[0] > 1
    got = _value_and_grads(lambda x, p: layers.moe(x, p, cfg)[0], x, p)
    want = _value_and_grads(lambda x, p: onehot_moe(x, p, cfg), x, p)
    for name in want:
        assert got[name].dtype == want[name].dtype, name
        assert _rel(got[name], want[name]) <= TOL[dtype], name


def test_backward_scatters_no_activation_rows():
    """The layer's gradient moves D-wide rows by gathers only; scatters
    are left to int32 index maps and to routing's (T, E) tensors."""
    cfg = _cfg("groups", "float32", "einsum")
    x, p = _inputs(cfg)
    grad = jax.jit(jax.grad(
        lambda x, p: jnp.sum(layers.moe(x, p, cfg)[0]), argnums=(0, 1)))
    text = grad.lower(x, p).as_text(dialect="hlo")
    scatters = [line for line in text.splitlines()
                if re.search(r"= \S+ scatter\(", line)]
    assert scatters, "the index-map build should be a scatter"
    for line in scatters:
        out = re.match(r"\s*%?\S+ = (\w+)\[([\d,]*)\]", line)
        dtype, dims = out.group(1), out.group(2).split(",")
        assert not (dtype.startswith(("f", "bf")) and dims[-1] == str(D)), \
            line
