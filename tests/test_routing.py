"""Locality-aware MoE routing tests (the paper's scheduler, in-graph)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st

from repro.core import topology
from repro.core.routing import (RoutingConfig, expert_steal_table, route,
                                slot_maps)

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
