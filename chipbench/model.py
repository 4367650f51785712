"""A configuration file (``chipbench/configs/<name>.json``) as the program's
``ModelConfig``, and the seed's parameter key.

The file holds the published ``config.json`` keys as they are run; this
module maps them onto the program's schema and nothing else.  A key the
mapping does not know is an error, so a new configuration cannot silently
run as something else.
"""

from __future__ import annotations

import json
import os

_HANDLED = {
    "name", "source", "family", "model_type", "hidden_size",
    "intermediate_size", "num_attention_heads", "num_key_value_heads",
    "num_hidden_layers", "vocab_size", "max_position_embeddings",
    "rope_theta", "hidden_act", "normalization_function", "layer_norm_eps",
    "rms_norm_eps", "tie_word_embeddings", "torch_dtype",
    "partial_rotary_factor", "use_qkv_bias", "qk_layernorm",
    "use_parallel_residual", "attention_bias", "num_local_experts",
    "num_experts_per_tok", "capacity_factor", "embedding_multiplier",
    "attention_multiplier", "residual_multiplier", "logits_scaling",
    "published", "departures", "deployment", "assumed", "notes",
}

NORMS = {"layernorm": "ln", "rmsnorm": "rms"}

# What the program's decoder implements and has no option for: a file that
# states another value cannot be run as stated.
_FIXED = {"layer_norm_eps": 1e-5, "rms_norm_eps": 1e-6,
          "partial_rotary_factor": 1.0, "use_qkv_bias": False,
          "attention_bias": False, "qk_layernorm": False,
          "use_parallel_residual": False, "embedding_multiplier": 1.0,
          "residual_multiplier": 1.0, "logits_scaling": 1.0}


def load(root: str, name: str) -> dict:
    path = os.path.join(root, "chipbench", "configs", f"{name}.json")
    with open(path) as f:
        conf = json.load(f)
    unknown = set(conf) - _HANDLED
    if unknown:
        raise ValueError(f"{path}: keys the benchmark cannot map: "
                         f"{sorted(unknown)}")
    if conf["name"] != name:
        raise ValueError(f"{path} names itself {conf['name']!r}")
    return conf


def model_config(conf: dict):
    """The program's ``ModelConfig`` for the configuration as run."""
    from repro.configs.base import ModelConfig, MoEConfig

    d, heads = conf["hidden_size"], conf["num_attention_heads"]
    moe = None
    if conf["family"] == "moe":
        moe = MoEConfig(n_experts=conf["num_local_experts"],
                        top_k=conf["num_experts_per_tok"],
                        expert_d_ff=conf["intermediate_size"],
                        capacity_factor=conf["capacity_factor"])
    if conf["hidden_act"] != "silu":
        raise ValueError(f"hidden_act {conf['hidden_act']!r} is not mapped")
    for key, value in _FIXED.items():
        if key in conf and conf[key] != value:
            raise ValueError(f"{conf['name']}: the program runs {key}="
                             f"{value}, the file states {conf[key]}")
    if conf.get("attention_multiplier", (d // heads) ** -0.5) \
            != (d // heads) ** -0.5:
        raise ValueError(f"{conf['name']}: the program scales attention "
                         f"scores by head_dim ** -0.5")
    return ModelConfig(
        name=conf["name"], family=conf["family"],
        n_layers=conf["num_hidden_layers"], d_model=d, n_heads=heads,
        n_kv_heads=conf["num_key_value_heads"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        head_dim=d // heads, mlp="swiglu",
        norm=NORMS[conf["normalization_function"]],
        rope_theta=float(conf["rope_theta"]),
        tie_embeddings=conf["tie_word_embeddings"], moe=moe,
        dtype=conf["torch_dtype"])


def param_key(seed: int):
    """The parameters' PRNG key: the seed's low 32 bits make the key and the
    bits above them are folded in, so seeds past 2**32 stay distinct."""
    import jax
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


