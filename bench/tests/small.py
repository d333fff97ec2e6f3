"""Small same-family configurations for CPU tests of the benchmark."""

from __future__ import annotations

import copy
import dataclasses

from bench import spec

SMALL_TRAIN = {"batch": 4, "seq_len": 32, "pool_batches": 4}
SMALL_SERVE = {"batch": 4, "prompt_lens": [16, 32, 48], "gen": 6,
               "checked_batches": 3}


def configs(name: str):
    """(program ArchConfig, config-file dict) at a small size, alike."""
    from repro import configs as pc

    f = copy.deepcopy(spec.config_file(name))
    base = pc.get(f["arch"]).reduced()
    if f.get("model_type") == "granitemoe":
        f.update(hidden_size=base.d_model, intermediate_size=base.moe_d_ff,
                 num_hidden_layers=2, num_attention_heads=base.num_heads,
                 num_key_value_heads=base.num_kv_heads,
                 num_local_experts=base.moe_num_experts,
                 num_experts_per_tok=base.moe_top_k,
                 vocab_size=base.vocab_size, torch_dtype="float32")
        f["program"] = dict(f["program"], moe_group=64)
        cfg = dataclasses.replace(
            base, num_layers=2, rope_theta=f["rope_theta"],
            norm_eps=f["rms_norm_eps"], **f["program"])
    else:
        f.update(d_model=base.d_model, n_layer=2, d_state=base.ssm_state,
                 headdim=base.ssm_head_dim, vocab_size=base.vocab_size,
                 chunk_size=8, dtype="float32")
        cfg = dataclasses.replace(base, num_layers=2, ssm_chunk=8,
                                  norm_eps=f["norm_eps"], remat="none")
    return cfg, f


def mix(name: str, **over):
    m = dict(spec.traffic(name))
    m.update(SMALL_TRAIN if m["driver"] == "train" else SMALL_SERVE)
    m.update(over)
    return m
