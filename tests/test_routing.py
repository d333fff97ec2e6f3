"""Locality-aware MoE routing tests (the paper's scheduler, in-graph)."""

import jax
import jax.extend as jex
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st

from repro.core import topology
from repro.core.routing import (RoutingConfig, expert_steal_table, route,
                                slot_maps)
from repro.core.stealing import steal_order_matrix
from repro.launch.mesh import line_steal_table

TOPO = topology.tpu_pod_2d(4, 4)
TABLE = expert_steal_table(TOPO, np.arange(16), "dfwspt")


def _logits(t=128, e=16, skew=None, seed=0):
    x = jax.random.normal(jax.random.PRNGKey(seed), (t, e))
    if skew is not None:
        x = x.at[:, skew].add(3.0)
    return x


def test_steal_table_sorted_by_distance():
    d = TOPO.core_distance_matrix()
    for e in range(16):
        hops = [d[e, v] for v in TABLE[e]]
        assert hops == sorted(hops)
        assert set(TABLE[e].tolist()) == set(range(16)) - {e}


def test_dfwsrpt_randomizes_ties_only():
    t1 = expert_steal_table(TOPO, np.arange(16), "dfwsrpt", seed=0)
    t2 = expert_steal_table(TOPO, np.arange(16), "dfwsrpt", seed=1)
    d = TOPO.core_distance_matrix()
    for e in range(16):
        assert [d[e, v] for v in t1[e]] == [d[e, v] for v in t2[e]]
    assert (t1 != t2).any()        # ties actually shuffled


@pytest.mark.parametrize("policy", ["dfwspt", "dfwsrpt", "dfwshier"])
def test_expert_steal_table_is_the_victim_order_of_the_owners(policy):
    """Experts are the stealing module's threads, on their owners' cores:
    two experts per device here, so each one's first victim is the other
    expert of its own device."""
    owners = np.arange(16) // 2
    table = expert_steal_table(TOPO, owners, policy, seed=5)
    np.testing.assert_array_equal(
        table, steal_order_matrix(TOPO, owners, policy, seed=5))
    assert (table[:, 0] == np.arange(16) ^ 1).all()


@pytest.mark.parametrize("E", [8, 32])
def test_line_table_is_the_torus_rule(E):
    """The table a one-device step routes by: the others by hop distance
    min(|i-j|, E-|i-j|) on a ring of E chips, ties by the lower id."""
    want = [sorted((j for j in range(E) if j != i),
                   key=lambda j: (min(abs(i - j), E - abs(i - j)), j))
            for i in range(E)]
    assert line_steal_table(E).tolist() == want


def test_route_without_table_walks_the_ring():
    cfg = RoutingConfig(16, top_k=2, capacity=8, steal_attempts=3)
    logits = _logits(t=128, skew=[0, 1], seed=4)
    ring = np.stack([(e + np.arange(1, 16)) % 16 for e in range(16)])
    got, want = route(logits, cfg), route(logits, cfg, ring)
    for key in ("expert", "slot", "weight"):
        np.testing.assert_array_equal(np.asarray(got[key]),
                                      np.asarray(want[key]))
    top = np.asarray(jax.lax.top_k(logits, 2)[1])
    expert = np.asarray(got["expert"])
    assert ((expert >= 0) & (expert != top)).any(), "no pair was stolen"


def test_no_overflow_no_steals():
    cfg = RoutingConfig(16, top_k=1, capacity=128, steal_attempts=3)
    logits = _logits()
    r = route(logits, cfg, TABLE)
    top1 = jnp.argmax(logits, axis=1)
    np.testing.assert_array_equal(np.asarray(r["expert"][:, 0]),
                                  np.asarray(top1))
    assert float(r["drop_fraction"]) == 0.0


def test_stealing_reduces_drops():
    skewed = _logits(skew=[0, 1])
    base = route(skewed, RoutingConfig(16, 1, 16, steal_attempts=0))
    stolen = route(skewed, RoutingConfig(16, 1, 16, steal_attempts=3),
                   TABLE)
    assert float(stolen["drop_fraction"]) < float(base["drop_fraction"])


def test_capacity_never_exceeded():
    cfg = RoutingConfig(16, top_k=2, capacity=8, steal_attempts=2)
    r = route(_logits(t=256, seed=1), cfg, TABLE)
    e = np.asarray(r["expert"]).ravel()
    s = np.asarray(r["slot"]).ravel()
    for ex in range(16):
        slots = s[e == ex]
        assert len(slots) <= 8
        assert len(set(slots.tolist())) == len(slots)   # unique slots
        assert (slots < 8).all() and (slots >= 0).all()


def test_weights_normalized_over_kept():
    cfg = RoutingConfig(16, top_k=4, capacity=4, steal_attempts=1)
    r = route(_logits(t=200, seed=2), cfg, TABLE)
    w = np.asarray(r["weight"])
    kept = np.asarray(r["expert"]) >= 0
    sums = w.sum(-1)
    has_any = kept.any(-1)
    np.testing.assert_allclose(sums[has_any], 1.0, rtol=1e-5)
    assert (w[~kept] == 0).all()


def test_stolen_tokens_go_to_nearest_free():
    """All overflow from expert 0 must land on its steal-order prefix."""
    cfg = RoutingConfig(16, top_k=1, capacity=8, steal_attempts=1)
    logits = jnp.full((32, 16), -5.0).at[:, 0].set(5.0)
    r = route(logits, cfg, TABLE)
    e = np.asarray(r["expert"][:, 0])
    moved = e[(e >= 0) & (e != 0)]
    assert set(moved.tolist()) <= {int(TABLE[0, 0])}
    assert (e == 0).sum() == 8     # expert 0 exactly at capacity


def _route_by_cumsum(gate_logits, cfg, steal_table=None):
    """``route``'s placement as first written: each attempt counts a pair's
    place in its expert's queue by a cumsum along the pairs, and reads the
    (E,) tables by element gathers. Returns route's arrays and the pairs
    each attempt placed."""
    T, E = gate_logits.shape
    probs = jax.nn.softmax(gate_logits.astype(jnp.float32), axis=-1)
    top_p, top_e = jax.lax.top_k(probs, cfg.top_k)
    if steal_table is None:
        steal_table = (np.arange(E)[:, None] + np.arange(1, E)) % E
    table = jnp.asarray(steal_table, jnp.int32)
    choice = top_e.T.reshape(-1)
    active = jnp.ones(choice.shape, bool)
    expert = jnp.full(choice.shape, -1, jnp.int32)
    slot = jnp.full(choice.shape, -1, jnp.int32)
    used = jnp.zeros((E,), jnp.int32)
    placed_per_attempt = []
    for attempt in range(cfg.steal_attempts + 1):
        onehot = (jax.nn.one_hot(choice, E, dtype=jnp.int32)
                  * active[:, None].astype(jnp.int32))
        pos_in_expert = jnp.cumsum(onehot, axis=0) - onehot
        pos = jnp.take_along_axis(
            pos_in_expert, choice[:, None], axis=1)[:, 0] + used[choice]
        placed = active & (pos < cfg.capacity)
        used = used + jnp.minimum(onehot.sum(axis=0), cfg.capacity - used)
        expert = jnp.where(placed, choice, expert)
        slot = jnp.where(placed, pos, slot)
        active = active & ~placed
        placed_per_attempt.append(placed.sum())
        if attempt < cfg.steal_attempts:
            choice = table[choice, attempt]
    expert = expert.reshape(cfg.top_k, T).T
    slot = slot.reshape(cfg.top_k, T).T
    keep = expert >= 0
    w = top_p * keep
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    return (dict(expert=expert, slot=slot, weight=w,
                 drop_fraction=1.0 - jnp.mean(keep.astype(jnp.float32))),
            jnp.stack(placed_per_attempt))


def _fill_case(e, k, t, cap_factor, attempts, seed=0):
    """Logits that pile onto every fourth expert, the first most, so their
    nearest victims have room; capacity ``cap_factor`` x the even share of
    the T·K pairs."""
    cfg = RoutingConfig(e, top_k=k, steal_attempts=attempts,
                        capacity=max(1, int(np.ceil(cap_factor * t * k / e))))
    x = jax.random.normal(jax.random.PRNGKey(seed), (t, e))
    return cfg, x.at[:, ::4].add(jnp.linspace(6.0, 3.0, len(range(0, e, 4))))


def _assert_same_fill(got, want):
    for key in ("expert", "slot", "weight", "drop_fraction"):
        np.testing.assert_array_equal(np.asarray(got[key]),
                                      np.asarray(want[key]), err_msg=key)


@pytest.mark.parametrize("table", [None, "line"])
@pytest.mark.parametrize("attempts", [0, 1, 2])
@pytest.mark.parametrize("cap_factor", [1.25, 0.5])
@pytest.mark.parametrize("t", [8, 200, 4096])
@pytest.mark.parametrize("e,k", [(8, 2), (16, 1), (32, 8)])
def test_route_places_every_pair_where_the_cumsum_fill_did(
        e, k, t, cap_factor, attempts, table):
    cfg, x = _fill_case(e, k, t, cap_factor, attempts, seed=t + attempts)
    tbl = line_steal_table(e) if table else None
    want, placed = _route_by_cumsum(x, cfg, tbl)
    _assert_same_fill(route(x, cfg, tbl), want)
    if cap_factor < 1:
        assert float(want["drop_fraction"]) > 0
        if t > 8:   # 8 tokens are too few pairs to overflow three times
            assert (np.asarray(placed) > 0).all(), np.asarray(placed)


@pytest.mark.parametrize("cap_factor", [1.25, 0.5])
@pytest.mark.parametrize("t", [8, 200, 4096])
@pytest.mark.parametrize("e,k", [(8, 2), (16, 1), (32, 8)])
def test_route_under_vmap_fills_each_group_as_the_cumsum_fill(
        e, k, t, cap_factor):
    """Groups routed side by side, as ``layers.moe`` vmaps them."""
    cfg, x0 = _fill_case(e, k, t, cap_factor, 2, seed=1)
    _, x1 = _fill_case(e, k, t, cap_factor, 2, seed=2)
    tbl = line_steal_table(e)
    got = jax.vmap(lambda x: route(x, cfg, tbl))(jnp.stack([x0, x1]))
    for g, x in enumerate((x0, x1)):
        want, _ = _route_by_cumsum(x, cfg, tbl)
        _assert_same_fill({key: v[g] for key, v in got.items()}, want)


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else (v,):
                if isinstance(sub, jex.core.ClosedJaxpr):
                    yield from _eqns(sub.jaxpr)
                elif isinstance(sub, jex.core.Jaxpr):
                    yield from _eqns(sub)


def test_route_neither_cumsums_nor_gathers_along_the_pairs():
    """At granite's group (T 4096, K 8, E 32) a cumsum along the K·T pairs
    lowers to a reduce-window over the whole axis, and a gather of one
    element a pair runs far below bandwidth on a TPU: the fill has
    neither."""
    t, k, e = 4096, 8, 32
    cfg = RoutingConfig(e, top_k=k, capacity=1280, steal_attempts=2)
    tbl = line_steal_table(e)
    jaxpr = jax.make_jaxpr(lambda x: route(x, cfg, tbl))(
        jnp.zeros((t, e), jnp.float32)).jaxpr
    eqns = list(_eqns(jaxpr))
    assert any(q.primitive.name == "dot_general" for q in eqns)
    for q in eqns:
        name = q.primitive.name
        if name.startswith("cum"):
            shape = q.invars[0].aval.shape
            assert shape[q.params["axis"]] != t * k, (name, shape)
        if name == "gather":
            idx = q.invars[1].aval.shape
            assert int(np.prod(idx[:-1])) != t * k, (name, idx)


def _maps(cap=8, attempts=2, seed=3):
    """A skewed routing of 64 tokens to 8 experts, top 2, and its maps."""
    cfg = RoutingConfig(8, top_k=2, capacity=cap, steal_attempts=attempts)
    tbl = expert_steal_table(TOPO, np.arange(8) * 2, "dfwspt")
    r = route(_logits(t=64, e=8, skew=[0, 1], seed=seed), cfg, tbl)
    maps = slot_maps(r["expert"], r["slot"], 8, cap)
    return (np.asarray(r["expert"]), np.asarray(r["slot"]),
            *(np.asarray(m) for m in maps))


@pytest.mark.parametrize("cap,attempts", [(8, 0), (8, 2), (32, 1)])
def test_slot_maps_are_inverse_on_kept_pairs(cap, attempts):
    expert, slot, pair_of_slot, slot_of_pair = _maps(cap, attempts)
    kept = expert >= 0
    assert (slot_of_pair[kept] == (expert * cap + slot)[kept]).all()
    flat = slot_of_pair.reshape(-1)
    filled = pair_of_slot < flat.size
    # each filled slot holds one pair, and that pair names the slot back
    assert (flat[pair_of_slot[filled]] == np.flatnonzero(filled)).all()
    # each kept pair is the pair its slot holds
    pairs = np.flatnonzero(kept.reshape(-1))
    assert (pair_of_slot[flat[pairs]] == pairs).all()
    assert filled.sum() == kept.sum()


@pytest.mark.parametrize("cap,attempts", [(8, 0), (8, 2)])
def test_drops_and_empty_slots_map_to_the_sentinel(cap, attempts):
    expert, slot, pair_of_slot, slot_of_pair = _maps(cap, attempts)
    n_pairs, n_slots = expert.size, 8 * cap
    assert (expert < 0).any(), "the skew should overflow capacity"
    assert (slot_of_pair[expert < 0] == n_slots).all()
    assert ((slot_of_pair >= 0) & (slot_of_pair <= n_slots)).all()
    assert ((pair_of_slot >= 0) & (pair_of_slot <= n_pairs)).all()
    assert (pair_of_slot == n_pairs).sum() == n_slots - (expert >= 0).sum()


def test_slot_maps_of_stacked_groups_match_each_group():
    cfg = RoutingConfig(8, top_k=2, capacity=8, steal_attempts=2)
    tbl = expert_steal_table(TOPO, np.arange(8) * 2, "dfwspt")
    rs = [route(_logits(t=64, e=8, skew=[0, 1], seed=s), cfg, tbl)
          for s in (3, 4, 5)]
    stacked = slot_maps(jnp.stack([r["expert"] for r in rs]),
                        jnp.stack([r["slot"] for r in rs]), 8, 8)
    for i, r in enumerate(rs):
        for got, want in zip(stacked, slot_maps(r["expert"], r["slot"], 8, 8)):
            np.testing.assert_array_equal(np.asarray(got[i]),
                                          np.asarray(want))


@settings(max_examples=15, deadline=None)
@given(t=st.sampled_from([32, 64]), k=st.integers(1, 3),
       cap=st.sampled_from([4, 8, 32]), attempts=st.integers(0, 3),
       seed=st.integers(0, 5))
def test_routing_invariants_property(t, k, cap, attempts, seed):
    cfg = RoutingConfig(16, top_k=k, capacity=cap, steal_attempts=attempts)
    r = route(_logits(t=t, seed=seed), cfg, TABLE)
    e = np.asarray(r["expert"])
    s = np.asarray(r["slot"])
    # dropped ⇔ slot == -1
    assert ((e < 0) == (s < 0)).all()
    # total kept ≤ total capacity
    assert (e >= 0).sum() <= 16 * cap
    # per-(expert, slot) uniqueness
    pairs = [(int(a), int(b)) for a, b in zip(e.ravel(), s.ravel())
             if a >= 0]
    assert len(pairs) == len(set(pairs))
    assert np.isfinite(float(r["aux_loss"]))
