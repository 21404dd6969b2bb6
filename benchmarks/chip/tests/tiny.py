"""A tiny cell for the CPU tests: the harness's loaders pointed at
``tiny.json`` and ``tiny_traffic.json``, everything else as in a run."""
from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
for p in (CHIP, os.path.join(os.path.dirname(os.path.dirname(CHIP)), "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import spec  # noqa: E402
import workload  # noqa: E402

CELL = "tiny-moe.tiny"
RATE = 3.0


def _read(name):
    with open(os.path.join(HERE, name)) as f:
        return json.load(f)


def install(monkeypatch, limit=1.0):
    """Point the loaders at the tiny cell; returns its config."""
    bench = spec.load_benchmark()
    bench = dict(bench, workloads=[{"name": CELL, "config": "tiny-moe",
                                    "traffic": "tiny", "chips": 1,
                                    "why": "test"}])
    conf = _read("tiny.json")
    monkeypatch.setattr(spec, "load_benchmark", lambda path=None: bench)
    monkeypatch.setattr(spec, "load_config", lambda b, name: conf)
    monkeypatch.setattr(spec, "load_cell", lambda name: {
        "rate_rps": RATE, "check": {"p98_logit_gap": limit}})
    monkeypatch.setattr(workload, "load_traffic",
                        lambda name: _read("tiny_traffic.json"))
    return conf
