"""Useful operations and bytes, counted from the model and the live rows,
never from the implementation: a padded row, a dropless buffer's empty
expert slots, or a page past a row's context count for nothing. A change
that removes padding lowers the device time and leaves these counts.

``contexts`` is one decode step's live rows, each given as the number of
keys it attends over (its position + 1).
"""
from __future__ import annotations

from typing import Dict, Sequence

BF16_BYTES = 2


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


def roofline_seconds(flops: float, byts: float, peak: Dict) -> float:
    """The least time the chip needs: the larger of the compute and the
    memory bound."""
    return max(flops / peak["bf16_flops"], byts / peak["hbm_bytes_per_s"])
