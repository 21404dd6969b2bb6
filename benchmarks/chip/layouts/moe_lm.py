"""Layout ``moe_lm``: a decoder-only language model whose every layer is
attention followed by a top-k MoE of SwiGLU experts, with LoRA adapters on
the experts' gate, up and down projections (Mixtral, Qwen3-MoE). What the
benchmark knows of this parameter set is here: the published keys it
reads, the program's ``ModelConfig``, the leaves and their placement on a
mesh, and the useful work of a decode step.

Leaves (shared by the program and the reference):

  embed, lm_head         (V_pad, d)     V_pad = vocab rounded up to 256
  final_norm             (d,)           stored as gamma - 1
  layers.ln1, ln2        (L, d)         stored as gamma - 1
  layers.attn.wq         (L, d, H*hd)   wk, wv (L, d, KV*hd); wo (L, H*hd, d)
  layers.moe.router      (L, d, E)
  layers.moe.gate, up    (L, E, d, ff); down (L, E, ff, d)
  adapters[t].A, .B      t in gate/up: (L, N, E, d, r), (L, N, E, r, ff);
                         down: (L, N, E, ff, r), (L, N, E, r, d)

Each matrix is normal with standard deviation gain / sqrt(fan_in), the
gains taken from the configuration's ``init`` block.

Useful work counts the model and the live rows only, never the
implementation: a padded row, a dropless buffer's empty expert slots, or
a page past a row's context count for nothing. ``contexts`` is one decode
step's live rows, each given as the number of keys it attends over (its
position + 1).
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

BF16_BYTES = 2
EXPERT_LEAVES = ("layers.moe.gate", "layers.moe.up", "layers.moe.down")


def padded_vocab(vocab: int) -> int:
    return -(-vocab // 256) * 256


def dims(conf: Dict) -> Dict:
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
    m = dims(conf)
    return ModelConfig(
        name=conf["name"], family="moe", n_layers=m["n_layers"],
        d_model=m["d_model"], n_heads=m["n_heads"],
        n_kv_heads=m["n_kv_heads"], head_dim=m["head_dim"], d_ff=m["d_ff"],
        vocab_size=m["vocab"], rope_theta=m["rope_theta"],
        norm_eps=m["norm_eps"], sliding_window=m["window"],
        n_experts=m["n_experts"], top_k=m["top_k"], lora_rank=m["rank"],
        lora_targets=tuple(conf["lora"]["targets"]), dtype="bfloat16")


def shapes(m: Dict) -> Tuple[Dict, Dict]:
    """{path: (shape, fan_in, gain_key)} of the weights and the adapters,
    in the order they are drawn."""
    d, H, KV, hd = m["d_model"], m["n_heads"], m["n_kv_heads"], m["head_dim"]
    L, E, ff, r, N = (m["n_layers"], m["n_experts"], m["d_ff"], m["rank"],
                      m["n_adapters"])
    V = padded_vocab(m["vocab"])
    w = {
        "embed": ((V, d), None, "embed_std"),
        "lm_head": ((V, d), d, "lm_head_gain"),
        "final_norm": ((d,), None, "norm_std"),
        "layers.ln1": ((L, d), None, "norm_std"),
        "layers.ln2": ((L, d), None, "norm_std"),
        "layers.attn.wq": ((L, d, H * hd), d, "attn_gain"),
        "layers.attn.wk": ((L, d, KV * hd), d, "attn_gain"),
        "layers.attn.wv": ((L, d, KV * hd), d, "attn_gain"),
        "layers.attn.wo": ((L, H * hd, d), H * hd, "attn_out_gain"),
        "layers.moe.router": ((L, d, E), d, "router_gain"),
        "layers.moe.gate": ((L, E, d, ff), d, "expert_in_gain"),
        "layers.moe.up": ((L, E, d, ff), d, "expert_in_gain"),
        "layers.moe.down": ((L, E, ff, d), ff, "expert_out_gain"),
    }
    a = {}
    for t, (din, dout) in (("gate", (d, ff)), ("up", (d, ff)),
                           ("down", (ff, d))):
        a[f"{t}.A"] = ((L, N, E, din, r), din, "lora_a_gain")
        a[f"{t}.B"] = ((L, N, E, r, dout), r, "lora_b_gain")
    return w, a


def expert_dim(path: str) -> Optional[int]:
    """The dimension of a leaf that runs over the experts, where the
    program places it along the mesh's expert axis; None for a leaf that
    every chip holds whole (``distributed/steps.shard_serve_params``: the
    base experts' gate, up and down; the adapters' factors alike)."""
    if path in EXPERT_LEAVES:
        return 1
    if path.startswith("adapters."):
        return 2
    return None


def expert_axis(m: Dict, mesh_shape: Sequence[int]) -> Optional[str]:
    """The mesh axis the experts lie along: the decode rules put them on
    ``data``, where that axis has more than one chip and divides them."""
    data = mesh_shape[0]
    return "data" if data > 1 and m["n_experts"] % data == 0 else None


def lora_view_shapes(m: Dict) -> Dict[str, Tuple[int, ...]]:
    """The fused transport's device view of one LoRA server's slot pool
    (``transport/fused.DeviceLoraView``): gate and up fused side by side,
    (replicas, L, slots, E, d_in, r_view)."""
    L, E, d, ff, r, M = (m["n_layers"], m["n_experts"], m["d_model"],
                         m["d_ff"], m["rank"], m["n_adapters"])
    return {"up_A": (1, L, M, E, d, 2 * r), "up_B": (1, L, M, E, 2 * r, 2 * ff),
            "down_A": (1, L, M, E, ff, r), "down_B": (1, L, M, E, r, d)}


def decode_step_flops(m: Dict, contexts: Sequence[int]) -> float:
    """FLOPs of one decode step over its live rows: attention projections,
    attention over each row's real context, router, top_k experts' three
    GEMMs, the adapter's three expert hooks at rank r on those top_k
    experts, and the LM head. Norms, rotary and softmax are left out."""
    d, H, KV, hd = m["d_model"], m["n_heads"], m["n_kv_heads"], m["head_dim"]
    ff, E, K, r, V = m["d_ff"], m["n_experts"], m["top_k"], m["rank"], \
        m["vocab"]
    b = len(contexts)
    keys = sum(contexts)
    per_row_layer = (2 * d * (H * hd + 2 * KV * hd) + 2 * H * hd * d
                     + 2 * d * E
                     + K * 6 * d * ff
                     + K * 6 * r * (d + ff))
    per_layer = b * per_row_layer + 4 * H * hd * keys
    return m["n_layers"] * per_layer + b * 2 * d * V


def paged_attention_work(m: Dict, contexts: Sequence[int]):
    """(flops, bytes) of one paged-attention call (one layer) over the live
    rows: each row reads its real context's K and V once, its query, and
    writes its float32 output."""
    H, KV, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    keys = sum(contexts)
    b = len(contexts)
    flops = 4 * H * hd * keys
    byts = keys * 2 * KV * hd * BF16_BYTES + b * H * hd * (BF16_BYTES + 4)
    return float(flops), float(byts)
