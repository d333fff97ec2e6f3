"""CPU tests of the ``self_ms.*`` readers (bench/metrics/): each reads the
scope of the program that it is named for, and the readers that list a
train cell together read every scope that the cell's step carries, so that
its layer metrics add up to the device's busy time per step.

    JAX_PLATFORMS=cpu python -m pytest bench/tests -n 6 --dist loadfile
"""

from __future__ import annotations

import re

import pytest

import jax
import jax.numpy as jnp

import small
from bench import scopes, spec

READERS = [m["name"] for m in spec.benchmark()["per_layer"]
           if m["name"].startswith("self_ms.")]
TRAIN_CELLS = [w["name"] for w in spec.benchmark()["workloads"]
               if spec.traffic(w["traffic"])["driver"] == "train"]
KEYS = scopes.SCOPES + (scopes.UNSCOPED, scopes.RECOMPUTE)
# a reader that reads more than its own scope
ALSO = {"head": ("embed",)}


def scopes_read(name: str) -> set[str]:
    """The scopes whose per-step times the reader ``name`` adds up: each
    scope is given a distinct power of two, so the sum names them."""
    ms = {k: float(2 ** i) for i, k in enumerate(KEYS)}
    got = spec.metric_reader(name)({"work": {"train_step": object()},
                                    "scope_ms": ms})
    bits = int(got)
    assert bits == got
    return {k for i, k in enumerate(KEYS) if bits >> i & 1}


@pytest.mark.parametrize("name", READERS)
def test_each_scope_reader_reads_the_scope_it_is_named_for(name):
    from repro.models.scopes import SCOPES

    scope = name.removeprefix("self_ms.")
    assert scope in SCOPES + (scopes.UNSCOPED, scopes.RECOMPUTE)
    assert scopes_read(name) == {scope, *ALSO.get(scope, ())}


def step_scopes(cell: str) -> set[str]:
    """The scopes on the op_names of the cell's train step, compiled on the
    CPU at a small size of its configuration."""
    from repro.launch.train import build_train_step
    from repro.models import model
    from repro.optim import AdamWConfig, adamw_init

    cfg, _ = small.configs(spec.cell(cell)["config"])
    mix = small.mix(spec.cell(cell)["traffic"])
    params = jax.eval_shape(lambda k: model.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    opt = AdamWConfig()
    state = jax.eval_shape(lambda p: adamw_init(p, opt), params)
    tokens = jax.ShapeDtypeStruct((mix["batch"], mix["seq_len"]), jnp.int32)
    text = jax.jit(build_train_step(cfg, opt, 1, None)).lower(
        params, state, None, {"tokens": tokens, "labels": tokens}
    ).compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', text)
    return {s for s in map(scopes.innermost, names) if s}


@pytest.mark.parametrize("cell", TRAIN_CELLS)
def test_the_readers_of_a_train_cell_read_every_scope_of_its_step(cell):
    read = set()
    for m in spec.benchmark()["per_layer"]:
        if m["name"] in READERS and cell in m.get("workloads", [cell]):
            read |= scopes_read(m["name"])
    carried = step_scopes(cell)
    assert carried, "the step carries no named scope"
    assert carried <= read, sorted(carried - read)
    assert {scopes.UNSCOPED, scopes.RECOMPUTE} <= read
