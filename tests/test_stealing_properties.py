"""Property tests for the NUMA-aware victim orders (hypothesis shim).

These pin the *contract* of ``stealing.victim_order`` /
``steal_order_matrix`` that both the paper's schedulers and the policy
layer's compiled victim plans rely on: every sweep is a permutation of
the other threads, sorted by non-decreasing hop distance, with
policy-specific tie handling inside each equal-distance group.
"""

import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st

from repro.core import topology
from repro.core.stealing import (priority_list, steal_order_matrix,
                                 victim_order)

TOPOS = [topology.sunfire_x4600(), topology.tpu_pod_2d(2, 4),
         topology.uma(8)]
POLICIES = ("dfwspt", "dfwsrpt", "dfwshier")


def _setup(topo_i, T, thread_raw, seed):
    topo = TOPOS[topo_i]
    T = min(T, topo.num_cores)
    cores = list(range(T))
    return topo, cores, thread_raw % T, np.random.RandomState(seed)


@settings(max_examples=40, deadline=None)
@given(topo_i=st.integers(0, len(TOPOS) - 1), T=st.integers(2, 16),
       thread_raw=st.integers(0, 15), seed=st.integers(0, 5),
       policy=st.sampled_from(POLICIES))
def test_victim_order_is_permutation_of_others(topo_i, T, thread_raw,
                                               seed, policy):
    topo, cores, thread, rng = _setup(topo_i, T, thread_raw, seed)
    order = victim_order(topo, cores, thread, policy, rng)
    assert sorted(order) == [t for t in range(len(cores)) if t != thread]


@settings(max_examples=40, deadline=None)
@given(topo_i=st.integers(0, len(TOPOS) - 1), T=st.integers(2, 16),
       thread_raw=st.integers(0, 15), seed=st.integers(0, 5),
       policy=st.sampled_from(POLICIES))
def test_victim_order_distance_non_decreasing(topo_i, T, thread_raw,
                                              seed, policy):
    topo, cores, thread, rng = _setup(topo_i, T, thread_raw, seed)
    dist = topo.core_distance_matrix()
    order = victim_order(topo, cores, thread, policy, rng)
    ds = [dist[cores[thread], cores[v]] for v in order]
    assert ds == sorted(ds)


@settings(max_examples=25, deadline=None)
@given(topo_i=st.integers(0, len(TOPOS) - 1), T=st.integers(2, 16),
       thread_raw=st.integers(0, 15))
def test_dfwspt_ties_ascend_by_id(topo_i, T, thread_raw):
    """Within each equal-distance group DFWSPT victims ascend by id, and
    the order is static (rng-independent, equal to priority_list)."""
    topo, cores, thread, rng = _setup(topo_i, T, thread_raw, 0)
    dist = topo.core_distance_matrix()
    order = victim_order(topo, cores, thread, "dfwspt", rng)
    for a, b in zip(order, order[1:]):
        da = dist[cores[thread], cores[a]]
        db = dist[cores[thread], cores[b]]
        if da == db:
            assert a < b
    assert order == priority_list(topo, cores, thread)
    assert order == victim_order(topo, cores, thread, "dfwspt",
                                 np.random.RandomState(123))


@settings(max_examples=25, deadline=None)
@given(topo_i=st.integers(0, len(TOPOS) - 1), T=st.integers(2, 16),
       thread_raw=st.integers(0, 15), seed=st.integers(0, 5))
def test_dfwsrpt_permutes_only_within_distance_groups(topo_i, T,
                                                      thread_raw, seed):
    """DFWSRPT's randomization never crosses a distance boundary: the
    *set* of victims in each equal-distance segment matches DFWSPT's."""
    topo, cores, thread, rng = _setup(topo_i, T, thread_raw, seed)
    dist = topo.core_distance_matrix()
    rand = victim_order(topo, cores, thread, "dfwsrpt", rng)
    static = priority_list(topo, cores, thread)

    def groups(order):
        by_d = {}
        for v in order:
            by_d.setdefault(int(dist[cores[thread], cores[v]]),
                            set()).add(v)
        return by_d

    assert groups(rand) == groups(static)


@settings(max_examples=25, deadline=None)
@given(topo_i=st.integers(0, len(TOPOS) - 1), T=st.integers(2, 16),
       thread_raw=st.integers(0, 15), seed=st.integers(0, 5))
def test_dfwshier_node_members_contiguous(topo_i, T, thread_raw, seed):
    """DFWSHIER probes one node's victims contiguously (id asc) before
    moving on — no node appears in two separate runs."""
    topo, cores, thread, rng = _setup(topo_i, T, thread_raw, seed)
    order = victim_order(topo, cores, thread, "dfwshier", rng)
    runs = []  # (node, [victims...]) runs in sweep order
    for v in order:
        node = int(topo.core_node[cores[v]])
        if runs and runs[-1][0] == node:
            runs[-1][1].append(v)
        else:
            runs.append((node, [v]))
    assert len({node for node, _ in runs}) == len(runs)
    for _, vs in runs:
        assert vs == sorted(vs)


def _plan_sweep(policy, topo, cores, thread, seed):
    """The Python engine's first sweep of the compiled VictimPlan, drawn
    from a fresh RandomState(seed) as the engine draws it."""
    from repro.core.sim import SCHEDULERS
    from repro.core.sim.policy import compile_victim_plan
    plan = compile_victim_plan(SCHEDULERS[policy], topo, cores)
    rng = np.random.RandomState(seed)
    swept = []
    for tag, payload in plan.py_groups[thread]:
        if tag == 0:
            swept.extend(payload)
        elif tag == 1:
            g = list(payload)
            rng.shuffle(g)
            swept.extend(g)
        else:
            units = list(payload)
            rng.shuffle(units)
            for u in units:
                swept.extend(u)
    return swept


@settings(max_examples=20, deadline=None)
@given(topo_i=st.integers(0, len(TOPOS) - 1), T=st.integers(2, 16),
       thread_raw=st.integers(0, 15), seed=st.integers(0, 5))
def test_dfwshier_matches_compiled_plan_sweep(topo_i, T, thread_raw, seed):
    """victim_order('dfwshier') from a fresh RandomState(seed) equals
    the engine's first sweep of the compiled VictimPlan for that seed —
    the ahead-of-time form and the simulator agree."""
    topo, cores, thread, _ = _setup(topo_i, T, thread_raw, seed)
    got = victim_order(topo, cores, thread, "dfwshier",
                       np.random.RandomState(seed))
    assert got == _plan_sweep("dfwshier", topo, cores, thread, seed)


# (topology index, T, thread, seed): every topology, a thread at either
# end and inside, T below and at the topology's size
SWEEP_EXAMPLES = [(0, 16, 0, 0), (0, 16, 7, 3), (0, 9, 8, 5), (1, 8, 0, 1),
                  (1, 8, 5, 4), (1, 6, 3, 2), (2, 8, 2, 0), (2, 5, 4, 5)]


@pytest.mark.parametrize("policy", POLICIES)
def test_victim_order_matches_compiled_plan_sweep(policy):
    """For each paper policy, the router's order (victim_order, and row
    ``thread`` of steal_order_matrix when it is the first row drawn) is
    the simulator engine's first sweep."""
    for topo_i, T, thread, seed in SWEEP_EXAMPLES:
        topo, cores, thread, _ = _setup(topo_i, T, thread, seed)
        want = _plan_sweep(policy, topo, cores, thread, seed)
        assert victim_order(topo, cores, thread, policy,
                            np.random.RandomState(seed)) == want
        if thread == 0:
            row = steal_order_matrix(topo, cores, policy, seed=seed)[0]
            assert row.tolist() == want


@pytest.mark.parametrize("policy", POLICIES)
@pytest.mark.parametrize("topo", TOPOS, ids=lambda t: t.name)
def test_steal_order_matrix_rows(topo, policy):
    """Each row is that thread's victim permutation, distance-sorted,
    and the whole matrix is reproducible from its seed."""
    T = min(8, topo.num_cores)
    cores = list(range(T))
    dist = topo.core_distance_matrix()
    m = steal_order_matrix(topo, cores, policy, seed=3)
    assert m.shape == (T, T - 1)
    for th in range(T):
        row = [int(v) for v in m[th]]
        assert sorted(row) == [t for t in range(T) if t != th]
        ds = [dist[cores[th], cores[v]] for v in row]
        assert ds == sorted(ds)
    assert np.array_equal(m, steal_order_matrix(topo, cores, policy, seed=3))
