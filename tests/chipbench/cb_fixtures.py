"""Small cells on disk for the chip benchmark's CPU tests: a checkout root
holding ``BENCHMARK.json`` and the data files the cells name, at sizes the
CPU and the Pallas interpreter run in seconds."""

from __future__ import annotations

import json
import os

TINY_DENSE = {
    "name": "tiny-dense", "source": "test fixture", "family": "dense",
    "model_type": "stablelm", "hidden_size": 64, "intermediate_size": 128,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "num_hidden_layers": 2, "vocab_size": 512,
    "max_position_embeddings": 256, "rope_theta": 10000,
    "hidden_act": "silu", "normalization_function": "layernorm",
    "layer_norm_eps": 1e-5, "tie_word_embeddings": False,
    "torch_dtype": "bfloat16",
    "deployment": {"t_max": 128, "max_slots": 2}}

TINY_MOE = {
    "name": "tiny-moe", "source": "test fixture", "family": "moe",
    "model_type": "granitemoe", "hidden_size": 32, "intermediate_size": 64,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_hidden_layers": 2, "num_local_experts": 4,
    "num_experts_per_tok": 2, "capacity_factor": 2.0, "vocab_size": 250,
    "max_position_embeddings": 256, "rope_theta": 10000,
    "hidden_act": "silu", "normalization_function": "rmsnorm",
    "rms_norm_eps": 1e-6, "tie_word_embeddings": True,
    "torch_dtype": "bfloat16",
    "deployment": {"t_max": 128, "max_slots": 3, "pool_pages": 4}}

SHORT_PROMPTS = {"instruction_tokens": 8, "chunk_tokens": 16,
                 "chunks": {"median": 2, "sigma": 0.5, "min": 1, "max": 3}}

CLOSED = {"kind": "closed_loop", "rounds": 3, "prompt": SHORT_PROMPTS,
          "output": {"median": 4, "sigma": 0.5, "min": 2, "max": 8}}

OPEN = {"kind": "open_loop", "rate_per_s": 4.0, "prompt": SHORT_PROMPTS,
        "output": {"median": 3, "sigma": 0.5, "min": 2, "max": 6}}

E2E = [
    {"name": "output_tokens_per_s", "unit": "tokens/s", "better": "higher",
     "bound": 0.1, "source": "host_clock"},
    {"name": "itl_p95_s", "unit": "s", "better": "lower", "bound": 0.1,
     "source": "host_clock"},
    {"name": "ttft_p95_s", "unit": "s", "better": "lower", "bound": 0.1,
     "source": "host_clock", "workloads": ["tiny-moe.open"]},
    {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.25,
     "source": "host_clock"}]


def write_root(root, cells=(("tiny-dense.closed", TINY_DENSE, "closed", CLOSED),
                            ("tiny-moe.open", TINY_MOE, "open", OPEN)),
               gap_limit=1.0):
    """Write a checkout root with ``cells`` (name, config, traffic name,
    traffic) and return it."""
    root = str(root)
    for sub in ("configs", "traffic", "limits"):
        os.makedirs(os.path.join(root, "chipbench", sub), exist_ok=True)
    workloads = []
    for name, conf, tname, traffic in cells:
        _dump(os.path.join(root, "chipbench", "configs",
                           f"{conf['name']}.json"), conf)
        _dump(os.path.join(root, "chipbench", "traffic", f"{tname}.json"),
              traffic)
        _dump(os.path.join(root, "chipbench", "limits", f"{name}.json"),
              {"logit_gap": {"limit": gap_limit}})
        workloads.append({"name": name, "config": conf["name"],
                          "traffic": tname, "chips": 1, "why": "test"})
    bench = {"command": ["python3", "chipbench/run.py"],
             "paths": ["chipbench", "tests/chipbench"], "run_seconds": 2,
             "configs": [], "workloads": workloads, "end_to_end": E2E,
             "per_layer": [{"name": "decode_slots_mean", "unit": "slots",
                            "better": "higher", "source": "program_counter",
                            "layer": "serving engine",
                            "moves": "output_tokens_per_s"}]}
    _dump(os.path.join(root, "BENCHMARK.json"), bench)
    return root


def _dump(path, obj):
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)
