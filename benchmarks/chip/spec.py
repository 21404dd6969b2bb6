"""Where the benchmark's data lives, and how a configuration file becomes
the program's ``ModelConfig`` and ``ServeConfig``.

  BENCHMARK.json (checkout root)  cells, metrics, bounds
  configs/<config>.json           sizes as run, the published config
                                  beside them, cuts, departures; its
                                  ``layout`` and ``reference`` name the
                                  modules below, as paths under this
                                  directory
  layouts/<layout>.py             one parameter set: dims, ModelConfig,
                                  leaves and their placement, useful work
  reference/<layout>.py           its plain float32 reference
  traffic/<traffic>.json          one traffic mix (see workload.py)
  cells/<cell>.json               the cell's offered rate, knee sweep and
                                  correctness limits
  metrics/<metric>.py             one reader per per-layer metric
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
import sys
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


def load_module(rel: str):
    """The module at ``rel``, a path under the benchmark's directory: a
    configuration's layout and reference, a traffic kind's generator, a
    per-layer metric's reader. Loaded once per process."""
    path = CHIP_DIR / rel
    name = "bench." + rel.removesuffix(".py").replace("/", ".")
    if name not in sys.modules:
        if not path.is_file():
            raise FileNotFoundError(f"no module at {path}")
        found = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(found)
        sys.modules[name] = mod
        found.loader.exec_module(mod)
    return sys.modules[name]


def layout(conf: Dict):
    """The configuration's layout module (``layouts/<name>.py``): its
    dims, the program's ModelConfig, the leaves and their placement, and
    the useful work of a decode step."""
    return load_module(conf["layout"])


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
