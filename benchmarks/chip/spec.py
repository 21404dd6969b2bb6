"""Where the benchmark's data lives, and how a configuration file becomes
the program's ``ModelConfig`` and ``ServeConfig``.

  BENCHMARK.json (checkout root)  cells, metrics, bounds
  configs/<config>.json           sizes as run, the published config
                                  beside them, cuts, departures
  traffic/<traffic>.json          one traffic mix (see workload.py)
  cells/<cell>.json               the cell's offered rate, knee sweep and
                                  correctness limits
  metrics/<metric>.py             one reader per per-layer metric
"""
from __future__ import annotations

import json
import pathlib
from typing import Dict, Optional, Tuple

CHIP_DIR = pathlib.Path(__file__).resolve().parent
CHECKOUT = CHIP_DIR.parents[1]


def load_benchmark(path: Optional[pathlib.Path] = None) -> Dict:
    with open(path or CHECKOUT / "BENCHMARK.json") as f:
        return json.load(f)


def find_cell(bench: Dict, name: str) -> Dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def _read(path: pathlib.Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_config(bench: Dict, name: str) -> Dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return _read(CHECKOUT / c["file"])
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def load_cell(name: str) -> Dict:
    return _read(CHIP_DIR / "cells" / f"{name}.json")


def model_dims(conf: Dict) -> Dict:
    """The published keys that the harness, the counts and the reference
    read, under one set of names."""
    c = conf["config"]
    d, H = c["hidden_size"], c["num_attention_heads"]
    return {
        "d_model": d, "n_heads": H, "n_kv_heads": c["num_key_value_heads"],
        "head_dim": c.get("head_dim") or d // H,
        "d_ff": c.get("moe_intermediate_size") or c["intermediate_size"],
        "n_experts": c.get("num_local_experts") or c["num_experts"],
        "top_k": c["num_experts_per_tok"], "n_layers": c["num_hidden_layers"],
        "vocab": c["vocab_size"], "rope_theta": float(c["rope_theta"]),
        "norm_eps": float(c["rms_norm_eps"]),
        "window": int(c.get("sliding_window") or 0),
        "rank": conf["lora"]["rank"],
        "lora_scale": conf["lora"]["alpha"] / conf["lora"]["rank"],
        "n_adapters": conf["lora"]["n_adapters"],
    }


def model_config(conf: Dict):
    """The program's ModelConfig for this configuration file."""
    from repro.configs.base import ModelConfig
    m = model_dims(conf)
    return ModelConfig(
        name=conf["name"], family="moe", n_layers=m["n_layers"],
        d_model=m["d_model"], n_heads=m["n_heads"],
        n_kv_heads=m["n_kv_heads"], head_dim=m["head_dim"], d_ff=m["d_ff"],
        vocab_size=m["vocab"], rope_theta=m["rope_theta"],
        norm_eps=m["norm_eps"], sliding_window=m["window"],
        n_experts=m["n_experts"], top_k=m["top_k"], lora_rank=m["rank"],
        lora_targets=tuple(conf["lora"]["targets"]), dtype="bfloat16")


def max_len(conf: Dict, longest: Dict[str, int]) -> int:
    ps = conf["serve"]["page_size"]
    return -(-(longest["prompt"] + longest["output"]) // ps) * ps


def serve_config(conf: Dict, longest: Dict[str, int]):
    """The program's ServeConfig: one real-plane instance with paged KV."""
    from repro.serving.api import ServeConfig
    s = conf["serve"]
    mesh: Optional[Tuple[int, int]] = \
        tuple(s["mesh_shape"]) if s.get("mesh_shape") else None
    return ServeConfig(
        backend="cluster", disaggregated=s["plane"] == "disaggregated",
        transport=s["transport"], n_instances=1, max_batch=s["max_batch"],
        max_len=max_len(conf, longest), paged=True,
        page_size=s["page_size"], prefill_chunk=s["prefill_chunk"],
        adapter_cache_slots=conf["lora"]["n_adapters"], mesh_shape=mesh,
        prefetch=s.get("prefetch"))


def chips_of(conf: Dict) -> int:
    mesh = conf["serve"].get("mesh_shape")
    return mesh[0] * mesh[1] if mesh else 1
