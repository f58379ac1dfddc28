"""Tiny configurations and a repository layout for the benchmark's CPU
tests: the same files as the real cells, at widths a test run can hold."""
from __future__ import annotations

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

DENSE = {
    "name": "tiny-dense", "source": "test", "hidden_size": 64,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "intermediate_size": 128, "num_hidden_layers": 3, "vocab_size": 256,
    "rms_norm_eps": 1e-6, "rope_theta": 10000, "tie_word_embeddings": False,
    "torch_dtype": "bfloat16", "reference": "dense_gqa",
    "program": {"registry": "qwen3-4b", "overrides": {
        "tie_embeddings": False, "d_model": 64, "num_heads": 4,
        "num_kv_heads": 2, "head_dim": 16, "d_ff": 128, "vocab_size": 256,
        "rope_theta": 10000.0}},
    "serving": {
        "p_vendor": {"block_size": 16, "layout": "nhbd",
                     "kv_dtype": "bfloat16", "tp": 2},
        "d_vendor": {"block_size": 8, "layout": "nbhd",
                     "kv_dtype": "bfloat16", "tp": 1},
        "wire": {"kind": "raw", "dtype": "bfloat16"}, "prefill_chunk": 32,
        "max_batch": 4, "pool_blocks": 256},
    "check": {"first_logit_rel_l2_limit": 0.05,
              "decode_logit_rel_l2_limit": 0.05, "logit_gap_limit": 0.1,
              "sample_tokens": 24, "sample_max": 3},
}

OPEN_LOOP = {
    "driver": "open_loop_single",
    "arrivals": {"process": "poisson", "rate_rps": 8.0},
    "prompt": {"median": 40, "sigma": 0.5, "min": 16, "max": 96,
               "round_to": 16},
    "output": {"median": 6, "sigma": 0.5, "min": 2, "max": 12},
    "schedule_seed": 0,
}

def layout(root: str, configs: dict, mixes: dict, cells: list) -> str:
    """A checkout-like tree under ``root``: BENCHMARK.json, the given
    configuration and mix files, and the real metric readers. ``cells``:
    {"name", "config", "traffic", "like": a cell of the real benchmark}."""
    os.makedirs(os.path.join(root, "bench", "configs"), exist_ok=True)
    os.makedirs(os.path.join(root, "bench", "traffic"), exist_ok=True)
    shutil.copytree(os.path.join(REPO, "bench", "metrics"),
                    os.path.join(root, "bench", "metrics"),
                    dirs_exist_ok=True)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"] = []
    for name, conf in configs.items():
        path = f"bench/configs/{name}.json"
        with open(os.path.join(root, path), "w") as f:
            json.dump(conf, f)
        bench["configs"].append({"name": name, "source": "test",
                                 "file": path, "reduced": [], "why": "test"})
    for name, mix in mixes.items():
        with open(os.path.join(root, "bench", "traffic", name + ".json"),
                  "w") as f:
            json.dump(mix, f)
    # each test cell stands for the real cell it is ``like``, and takes
    # the metrics that cell has
    like = {w["name"]: w["like"] for w in cells}
    bench["workloads"] = [dict({k: v for k, v in w.items() if k != "like"},
                               chips=1, why="test") for w in cells]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [n for n, real in like.items()
                              if real in m["workloads"]]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root
