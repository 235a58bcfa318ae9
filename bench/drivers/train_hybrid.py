"""Training cells of the hybrid of Mamba2 and attention layers: the
training driver (``bench/drivers/train.py``), unchanged, with this
architecture's reference (``bench/reference_hybrid.py``) and its
configuration file's mapping in place of the dense decoder's.

Every key of the configuration file is read: it is checked against the
program's registered ``ArchConfig`` for the architecture (a key that
``reduced`` names sets its field instead), against what the program
implements, or is named below as not read, with the reason.  A key that
is none of these, or a value the program does not run, stops the run.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Any, Dict

from bench import reference_hybrid
from bench.harness import load_module

# configuration-file key -> the program's ArchConfig field
FIELDS = {"hidden_size": "d_model", "num_attention_heads": "n_heads",
          "num_key_value_heads": "n_kv_heads", "intermediate_size": "d_ff",
          "vocab_size": "vocab", "num_hidden_layers": "n_layers",
          "rms_norm_eps": "norm_eps", "tie_word_embeddings": "tie_embeddings",
          "torch_dtype": "dtype", "mamba_d_state": "ssm_state",
          "mamba_n_heads": "ssm_heads", "mamba_d_conv": "ssm_conv",
          "mamba_expand": "ssm_expand",
          "embedding_multiplier": "embedding_multiplier",
          "residual_multiplier": "residual_multiplier",
          "attention_multiplier": "attention_multiplier",
          "logits_scaling": "logits_scaling"}
# keys whose value the program implements and does not take as a field
FIXED = {"model_type": "granitemoehybrid", "hidden_act": "silu",
         "normalization_function": "rmsnorm", "attention_bias": False,
         "mamba_n_groups": 1, "mamba_conv_bias": True,
         "mamba_proj_bias": False, "position_embedding_type": "nope",
         "num_local_experts": 0, "num_experts_per_tok": 0}
# keys the program does not read, and why
UNREAD = {"rope_theta": "no position encoding (position_embedding_type)",
          "rope_scaling": "no position encoding (position_embedding_type)",
          "max_position_embeddings": "a bound on positions; nothing in the "
                                     "model depends on it without them"}
# the file's own description of itself
META = {"name", "arch", "source", "reduced", "published", "why_reduced",
        "assumed", "deployment"}


def program_config(config: Dict[str, Any]):
    """The program's ArchConfig for ``config["arch"]`` with the file's
    ``reduced`` keys put in its place, checked key by key against the
    file: every other key must equal the registered architecture's."""
    from repro.configs import get_config
    from repro.kernels.autotune import DEFAULTS
    from repro.models.ssm import CHUNK
    unknown = set(config) - set(FIELDS) - set(FIXED) - set(UNREAD) - META \
        - {"layer_types", "shared_intermediate_size", "mamba_d_head",
           "mamba_chunk_size"}
    if unknown:
        raise SystemExit(f"bench: configuration keys the program neither "
                         f"reads nor checks: {sorted(unknown)}")
    dm = reference_hybrid.Dims.of(config)
    arch = get_config(config["arch"])
    cut = set(config.get("reduced", ()))
    cfg = dataclasses.replace(
        arch, rope=False, head_dim=dm.head_dim,
        layer_pattern=dm.kinds[:dm.period],
        **{f: config[k] for k, f in FIELDS.items() if k in cut})
    expect = {**{k: getattr(arch, f) for k, f in FIELDS.items()
                 if k not in cut}, **FIXED,
              "layer_types": list(cfg.layer_pattern)
              * (len(config["layer_types"]) // dm.period),
              "shared_intermediate_size": cfg.d_ff,
              "mamba_d_head": cfg.d_inner // cfg.n_ssm_heads,
              "mamba_chunk_size": CHUNK}
    wrong = {k: (config[k], v) for k, v in expect.items()
             if k in config and config[k] != v}
    if (cfg.family != "hybrid" or arch.layer_pattern != cfg.layer_pattern
            or cfg.n_ssm_heads != config["mamba_n_heads"]
            or DEFAULTS["ssd"]["chunk"] != CHUNK or cfg.moe_experts
            or cfg.qkv_bias or cfg.attn_every or wrong):
        raise SystemExit(f"bench: the program's {cfg.name} does not run "
                         f"the configuration file (file, program): {wrong}")
    return cfg


def run(h) -> Dict[str, Any]:
    train = load_module(os.path.join(os.path.dirname(__file__), "train.py"),
                        "bench_driver_train_for_hybrid")
    train.ref = reference_hybrid
    train.program_config = program_config
    return train.run(h)
