"""Pure-jnp oracles for every Pallas kernel (the allclose reference).

bgmv / bgmv_expert / sgmv re-export the contracts from repro.core.lora_math;
gmm_ref is the grouped-GEMM oracle for the MoE expert kernel.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.lora_math import bgmv as bgmv_ref            # noqa: F401
from repro.core.lora_math import bgmv_expert as bgmv_expert_ref  # noqa: F401
from repro.core.lora_math import sgmv as sgmv_rowwise_ref    # noqa: F401

F32 = jnp.float32


def sgmv_ref(seg_rows, seg_adapter, A, B):
    """seg_rows: (S, cap, d_in); seg_adapter: (S,) (-1 = padding segment);
    A: (N, d_in, r); B: (N, r, d_out) -> (S, cap, d_out) f32."""
    ids = jnp.maximum(seg_adapter, 0)
    a = A[ids]                       # (S, d_in, r)
    b = B[ids]                       # (S, r, d_out)
    h = jnp.einsum("scd,sdr->scr", seg_rows.astype(F32), a.astype(F32))
    y = jnp.einsum("scr,sro->sco", h, b.astype(F32))
    return jnp.where((seg_adapter >= 0)[:, None, None], y, 0.0)


def sgmv_ranked_ref(seg_rows, seg_adapter, seg_rank, A, B):
    """``sgmv_ref`` with the shrink intermediate masked at each segment's
    true rank (``seg_rank``): h columns >= rank are forced to +0.0 before
    the expand — the oracle for kernels/sgmv.py ``sgmv_ranked``."""
    ids = jnp.maximum(seg_adapter, 0)
    a = A[ids]                       # (S, d_in, r)
    b = B[ids]                       # (S, r, d_out)
    h = jnp.einsum("scd,sdr->scr", seg_rows.astype(F32), a.astype(F32))
    r = A.shape[-1]
    h = jnp.where(jnp.arange(r)[None, None, :] < seg_rank[:, None, None],
                  h, 0.0)
    y = jnp.einsum("scr,sro->sco", h, b.astype(F32))
    return jnp.where((seg_adapter >= 0)[:, None, None], y, 0.0)


def sgmv_rank_grouped_ref(seg_rows, seg_adapter, seg_rank, A, B):
    """Oracle for ops.sgmv_rank_grouped: the bucketed dispatch computes
    the true-rank-masked SGMV, whatever the bucket layout (to f32
    rounding: a bucket sums over its sliced rank)."""
    return sgmv_ranked_ref(seg_rows, seg_adapter, seg_rank, A, B)


def bgmv_ranked_ref(x, A, B, ids, ranks):
    """``bgmv_ref`` bounded at each row's adapter true rank (``ranks`` is
    (N,) per-adapter) — the oracle for kernels/bgmv.py ``bgmv_ranked``."""
    N, _, r = A.shape
    safe = jnp.clip(ids, 0, N - 1)
    row_ranks = jnp.where(ids >= 0, jnp.asarray(ranks)[safe], 0)
    h = jnp.einsum("td,tdr->tr", x.astype(F32), A[safe].astype(F32))
    h = jnp.where(jnp.arange(r)[None, :] < row_ranks[:, None], h, 0.0)
    y = jnp.einsum("tr,tro->to", h, B[safe].astype(F32))
    return jnp.where((ids >= 0)[:, None], y, 0.0)


def fused_sgmv_ref(seg_rows, seg_slot, seg_eid, A, B):
    """seg_rows: (S, cap, d_in); seg_slot: (S,) slot ids (-1 = padding);
    seg_eid: (S,) expert per segment; A: (M, E, d_in, r);
    B: (M, E, r, d_out) -> (S, cap, d_out) f32 — the fused shrink-expand
    server-hook operator (kernels/fused.py)."""
    ids = jnp.maximum(seg_slot, 0)
    eids = jnp.maximum(seg_eid, 0)
    a = A[ids, eids]                 # (S, d_in, r)
    b = B[ids, eids]                 # (S, r, d_out)
    h = jnp.einsum("scd,sdr->scr", seg_rows.astype(F32), a.astype(F32))
    y = jnp.einsum("scr,sro->sco", h, b.astype(F32))
    return jnp.where((seg_slot >= 0)[:, None, None], y, 0.0)


def fused_sgmv_ranked_ref(seg_rows, seg_slot, seg_eid, seg_rank, A, B):
    """``fused_sgmv_ref`` with the VMEM intermediate masked at each
    segment's true rank — the oracle for kernels/fused.py
    ``fused_sgmv_ranked``."""
    ids = jnp.maximum(seg_slot, 0)
    eids = jnp.maximum(seg_eid, 0)
    a = A[ids, eids]                 # (S, d_in, r)
    b = B[ids, eids]                 # (S, r, d_out)
    h = jnp.einsum("scd,sdr->scr", seg_rows.astype(F32), a.astype(F32))
    r = A.shape[-1]
    h = jnp.where(jnp.arange(r)[None, None, :] < seg_rank[:, None, None],
                  h, 0.0)
    y = jnp.einsum("scr,sro->sco", h, b.astype(F32))
    return jnp.where((seg_slot >= 0)[:, None, None], y, 0.0)


def gmm_ref(xe, w, group_sizes=None):
    """xe: (E, C, d); w: (E, d, f) -> (E, C, f) f32; rows past
    group_sizes[e] are zeroed (ragged groups)."""
    y = jnp.einsum("ecd,edf->ecf", xe.astype(F32), w.astype(F32))
    if group_sizes is not None:
        C = xe.shape[1]
        mask = jnp.arange(C)[None, :] < group_sizes[:, None]
        y = jnp.where(mask[..., None], y, 0.0)
    return y


def paged_attention_ref(q, k_pool, v_pool, block_tables, pos, window=0):
    """q: (B, KV, G, hd); k/v pool: (P, page_size, KV, hd); block_tables:
    (B, nb) int32 (-1 = unallocated); pos: (B,) tokens already cached (the
    row attends over key positions 0..pos[b]; pos < 0 -> zeros).

    Materializes the gather (B, nb*page_size, KV, hd) — the memory traffic
    the Pallas kernel's index-map gather avoids — then runs one masked
    softmax. -> (B, KV, G, hd) f32.
    """
    B, KV, G, hd = q.shape
    P, ps = k_pool.shape[:2]
    nb = block_tables.shape[1]
    safe = jnp.clip(block_tables, 0, P - 1)
    k = k_pool[safe].reshape(B, nb * ps, KV, hd)
    v = v_pool[safe].reshape(B, nb * ps, KV, hd)
    kp = (jnp.arange(nb)[:, None] * ps + jnp.arange(ps)[None, :])
    kp = jnp.where(block_tables[:, :, None] >= 0, kp[None], -1)
    kp = kp.reshape(B, nb * ps)
    s = jnp.einsum("bkgd,bskd->bkgs", q.astype(F32), k.astype(F32))
    s = s / jnp.sqrt(jnp.asarray(hd, F32))
    valid = (kp >= 0) & (kp <= pos[:, None]) & (pos[:, None] >= 0)
    if window:
        valid &= kp > pos[:, None] - window
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    m = jnp.max(s, axis=-1)
    e = jnp.where(valid[:, None, None, :], jnp.exp(s - m[..., None]), 0.0)
    l = jnp.sum(e, axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", e, v.astype(F32))
    return o / jnp.maximum(l, 1e-20)[..., None]
