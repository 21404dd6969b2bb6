"""Layout of a toy architecture for the tests: ``moe_lm`` with one
always-on shared expert beside the routed experts in every layer (the
shape of Qwen2-MoE's and DeepSeek-MoE's shared experts, without their
gate), added as files only: this module, ``reference.py`` and
``toy.json``. The program has no shared expert, so it cannot serve this
layout: ``model_config`` gives the program's config of the routed part,
and the harness stops before it builds the system.

Leaves: those of ``moe_lm``, and
  layers.moe.shared_gate, shared_up   (L, d, ff_s); shared_down (L, ff_s, d)
on every chip of a mesh (no expert dimension).
"""
from __future__ import annotations

from typing import Dict, Sequence

import spec

base = spec.load_module("layouts/moe_lm.py")

model_config = base.model_config
expert_dim = base.expert_dim
expert_axis = base.expert_axis
lora_view_shapes = base.lora_view_shapes
paged_attention_work = base.paged_attention_work


def dims(conf: Dict) -> Dict:
    return dict(base.dims(conf),
                d_shared=conf["config"]["shared_expert_intermediate_size"])


def shapes(m: Dict):
    w, a = base.shapes(m)
    L, d, fs = m["n_layers"], m["d_model"], m["d_shared"]
    w["layers.moe.shared_gate"] = ((L, d, fs), d, "expert_in_gain")
    w["layers.moe.shared_up"] = ((L, d, fs), d, "expert_in_gain")
    w["layers.moe.shared_down"] = ((L, fs, d), fs, "expert_out_gain")
    return w, a


def decode_step_flops(m: Dict, contexts: Sequence[int]) -> float:
    """``moe_lm``'s, and the shared expert's three GEMMs for every row."""
    return base.decode_step_flops(m, contexts) + \
        m["n_layers"] * len(contexts) * 6 * m["d_model"] * m["d_shared"]
