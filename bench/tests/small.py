"""Small same-family configurations for CPU tests of the benchmark."""

from __future__ import annotations

import copy

import jax.numpy as jnp

from bench import kinds, spec
from bench.reference import model as ref_model

SMALL_TRAIN = {"batch": 4, "seq_len": 32, "pool_batches": 4}
SMALL_SERVE = {"batch": 4, "prompt_lens": [16, 32, 48], "gen": 6,
               "checked_batches": 3}


def configs(name: str):
    """(program ArchConfig, config-file dict) at a small size, alike."""
    from repro import configs as pc

    f = copy.deepcopy(spec.config_file(name))
    return kinds.kind(f).small(f, pc.get(f["arch"]).reduced())


def mix(name: str, **over):
    m = dict(spec.traffic(name))
    m.update(SMALL_TRAIN if m["driver"] == "train" else SMALL_SERVE)
    m.update(over)
    return m


def reference_logits(params, kind, s, tokens, table):
    """The reference's f32 logits at every position of tokens (B, L): each
    slot of the kind's period in order, in each repeat, then its head."""
    num = ref_model.Numerics()
    x = params["embed"][tokens].astype(jnp.float32)
    slots = kind.slots(s)
    for i in range(s.layers // len(slots)):
        for si, slot in enumerate(slots):
            x, _ = slot(x, ref_model.slot_params(params, si, i), s, num,
                        tokens.shape[1], table)
    return ref_model.logits(x, params, s, num, kind.head)
