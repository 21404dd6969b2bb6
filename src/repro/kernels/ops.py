"""Jit'd dispatch wrappers for the Pallas kernels.

On a TPU the kernels always compile natively through Mosaic: no setting
routes a TPU run to interpret mode or to the ``ref.py`` oracles. Elsewhere
the model code runs the pure-jnp oracles (interpret mode would be
needlessly slow inside big jits); ``REPRO_USE_PALLAS=1`` runs the kernels
instead, in interpret mode on the CPU, which executes the kernel body per
grid step — same math, same blocking (the kernel tests set it).

Padding: TPU lanes want the last dim % 128 == 0 and sublanes % 8 == 0; the
wrappers zero-pad r / d_out / cap as needed and slice back.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import bgmv as _bgmv
from repro.kernels import fused as _fused
from repro.kernels import gmm as _gmm
from repro.kernels import paged as _paged
from repro.kernels import ref as _ref
from repro.kernels import sgmv as _sgmv


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def kernels_enabled() -> bool:
    """Pallas kernels (not the ``ref.py`` oracles): always on a TPU; on
    other backends only when ``REPRO_USE_PALLAS=1``."""
    return on_tpu() or os.environ.get("REPRO_USE_PALLAS") == "1"


def pallas_interpret() -> bool:
    """Interpret mode is for the CPU backend only; a TPU compiles."""
    return jax.default_backend() == "cpu"


def _pad_to(x, mult, axis):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _bgmv_call(x, A, B, ids, interpret=True):
    d_out = B.shape[-1]
    x = _pad_to(x, 128, 1)
    A = _pad_to(_pad_to(A, 128, 1), 128, 2)
    B = _pad_to(_pad_to(B, 128, 1), 128, 2)
    out = _bgmv.bgmv(x, A, B, ids, interpret=interpret)
    return out[:, :d_out]


def bgmv(x, A, B, ids):
    if not kernels_enabled():
        return _ref.bgmv_ref(x, A, B, ids)
    return _bgmv_call(x, A, B, ids, interpret=pallas_interpret())


@functools.partial(jax.jit, static_argnames=("interpret",))
def _bgmv_ranked_call(x, A, B, ids, ranks, interpret=True):
    d_out = B.shape[-1]
    x = _pad_to(x, 128, 1)
    A = _pad_to(_pad_to(A, 128, 1), 128, 2)
    B = _pad_to(_pad_to(B, 128, 1), 128, 2)
    out = _bgmv.bgmv_ranked(x, A, B, ids, ranks, interpret=interpret)
    return out[:, :d_out]


def bgmv_ranked(x, A, B, ids, ranks):
    """``bgmv`` bounded at each row's adapter true rank (``ranks``: (N,))."""
    if not kernels_enabled():
        return _ref.bgmv_ranked_ref(x, A, B, ids, ranks)
    return _bgmv_ranked_call(x, A, B, ids, ranks,
                             interpret=pallas_interpret())


@functools.partial(jax.jit, static_argnames=("interpret",))
def _bgmv_expert_call(x, A, B, ids, eids, interpret=True):
    d_out = B.shape[-1]
    x = _pad_to(x, 128, 1)
    A = _pad_to(_pad_to(A, 128, 2), 128, 3)
    B = _pad_to(_pad_to(B, 128, 2), 128, 3)
    out = _bgmv.bgmv_expert(x, A, B, ids, eids, interpret=interpret)
    return out[:, :d_out]


def bgmv_expert(x, A, B, ids, eids):
    if not kernels_enabled():
        return _ref.bgmv_expert_ref(x, A, B, ids, eids)
    return _bgmv_expert_call(x, A, B, ids, eids, interpret=pallas_interpret())


@functools.partial(jax.jit, static_argnames=("interpret",))
def _sgmv_call(seg_rows, seg_adapter, A, B, interpret=True):
    d_out = B.shape[-1]
    seg_rows = _pad_to(_pad_to(seg_rows, 8, 1), 128, 2)
    A = _pad_to(_pad_to(A, 128, 1), 128, 2)
    B = _pad_to(_pad_to(B, 128, 1), 128, 2)
    out = _sgmv.sgmv(seg_rows, seg_adapter, A, B, interpret=interpret)
    return out[:, : seg_rows.shape[1], :d_out]


def sgmv(seg_rows, seg_adapter, A, B):
    if not kernels_enabled():
        return _ref.sgmv_ref(seg_rows, seg_adapter, A, B)
    cap = seg_rows.shape[1]
    out = _sgmv_call(seg_rows, seg_adapter, A, B, interpret=pallas_interpret())
    return out[:, :cap]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _sgmv_ranked_call(seg_rows, seg_adapter, seg_rank, A, B, interpret=True):
    d_out = B.shape[-1]
    seg_rows = _pad_to(_pad_to(seg_rows, 8, 1), 128, 2)
    A = _pad_to(_pad_to(A, 128, 1), 128, 2)
    B = _pad_to(_pad_to(B, 128, 1), 128, 2)
    out = _sgmv.sgmv_ranked(seg_rows, seg_adapter, seg_rank, A, B,
                            interpret=interpret)
    return out[:, : seg_rows.shape[1], :d_out]


def sgmv_ranked(seg_rows, seg_adapter, seg_rank, A, B):
    """``sgmv`` with per-segment true ranks (see kernels/sgmv.py)."""
    if not kernels_enabled():
        return _ref.sgmv_ranked_ref(seg_rows, seg_adapter, seg_rank, A, B)
    cap = seg_rows.shape[1]
    out = _sgmv_ranked_call(seg_rows, seg_adapter, seg_rank, A, B,
                            interpret=pallas_interpret())
    return out[:, :cap]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _sgmv_bucket_call(seg_rows, seg_adapter, A, B, interpret=True):
    # rank is already sliced + tile-padded by sgmv_rank_grouped (that IS
    # the saving); pad only the row/feature dims here
    d_out = B.shape[-1]
    seg_rows = _pad_to(_pad_to(seg_rows, 8, 1), 128, 2)
    A = _pad_to(A, 128, 1)
    B = _pad_to(B, 128, 2)
    out = _sgmv.sgmv(seg_rows, seg_adapter, A, B, interpret=interpret)
    return out[:, : seg_rows.shape[1], :d_out]


def sgmv_rank_grouped(seg_rows, seg_adapter, seg_rank, A, B):
    """Rank-bucketed SGMV: one dispatch per distinct true rank, with A/B
    sliced to that rank, so a rank-4 bucket prices rank-4 work instead of
    the pool rank. Feed it ``build_segments_ranked`` output (segments
    pre-sorted by rank so each bucket is a contiguous slice). Matches
    ``sgmv_rank_grouped_ref`` to f32 rounding: a bucket contracts over its
    sliced rank, which sums in a different order than the padded form."""
    if not kernels_enabled():
        return _ref.sgmv_rank_grouped_ref(seg_rows, seg_adapter, seg_rank,
                                          A, B)
    S, cap, _ = seg_rows.shape
    d_out = B.shape[-1]
    interp = pallas_interpret()
    # interpret mode has no lane constraint, so buckets shrink to the
    # sublane tile; native TPU lowering needs the contraction lane-aligned
    rmult = 8 if interp else 128
    ranks_np = np.asarray(seg_rank)
    active = np.asarray(seg_adapter) >= 0
    out = jnp.zeros((S, cap, d_out), jnp.float32)
    for rb in np.unique(ranks_np[active]).tolist():
        idx = np.nonzero(active & (ranks_np == rb))[0]
        rb_pad = -(-int(rb) // rmult) * rmult
        # a bucket's A/B slice may still carry other adapters' lanes up to
        # rb_pad — for this bucket's adapters those lanes are the pool's
        # exact-zero padding, so they contribute nothing
        got = _sgmv_bucket_call(seg_rows[idx], seg_adapter[idx],
                                _pad_to(A[:, :, :rb_pad], rmult, 2),
                                _pad_to(B[:, :rb_pad, :], rmult, 1),
                                interpret=interp)
        out = out.at[idx].set(got)
    return out


@functools.partial(jax.jit, static_argnames=("interpret",))
def _fused_sgmv_call(seg_rows, seg_slot, seg_eid, A, B, interpret=True):
    d_out = B.shape[-1]
    seg_rows = _pad_to(_pad_to(seg_rows, 8, 1), 128, 2)
    A = _pad_to(_pad_to(A, 128, 2), 128, 3)
    B = _pad_to(_pad_to(B, 128, 2), 128, 3)
    out = _fused.fused_sgmv(seg_rows, seg_slot, seg_eid, A, B,
                            interpret=interpret)
    return out[:, :, :d_out]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _fused_sgmv_ranked_call(seg_rows, seg_slot, seg_eid, seg_rank, A, B,
                            interpret=True):
    d_out = B.shape[-1]
    seg_rows = _pad_to(_pad_to(seg_rows, 8, 1), 128, 2)
    A = _pad_to(_pad_to(A, 128, 2), 128, 3)
    B = _pad_to(_pad_to(B, 128, 2), 128, 3)
    out = _fused.fused_sgmv_ranked(seg_rows, seg_slot, seg_eid, seg_rank,
                                   A, B, interpret=interpret)
    return out[:, :, :d_out]


def fused_sgmv_ranked(seg_rows, seg_slot, seg_eid, seg_rank, A, B):
    """``fused_sgmv`` with per-segment true ranks (see kernels/fused.py)."""
    if not kernels_enabled():
        return _ref.fused_sgmv_ranked_ref(seg_rows, seg_slot, seg_eid,
                                          seg_rank, A, B)
    cap = seg_rows.shape[1]
    out = _fused_sgmv_ranked_call(seg_rows, seg_slot, seg_eid, seg_rank,
                                  A, B, interpret=pallas_interpret())
    return out[:, :cap]


def fused_sgmv(seg_rows, seg_slot, seg_eid, A, B):
    """Fused shrink-expand server-hook operator over (slot, expert)
    segments — one launch per call (see kernels/fused.py)."""
    if not kernels_enabled():
        return _ref.fused_sgmv_ref(seg_rows, seg_slot, seg_eid, A, B)
    cap = seg_rows.shape[1]
    out = _fused_sgmv_call(seg_rows, seg_slot, seg_eid, A, B,
                           interpret=pallas_interpret())
    return out[:, :cap]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _gmm_call(xe, w, group_sizes, interpret=True):
    f = w.shape[-1]
    xe = _pad_to(_pad_to(xe, 8, 1), 128, 2)
    w = _pad_to(_pad_to(w, 128, 1), 128, 2)
    out = _gmm.gmm(xe, w, group_sizes, interpret=interpret)
    return out[:, :, :f]


def gmm(xe, w, group_sizes=None):
    if not kernels_enabled():
        return _ref.gmm_ref(xe, w, group_sizes)
    C = xe.shape[1]
    if group_sizes is None:
        group_sizes = jnp.full((xe.shape[0],), C, jnp.int32)
    out = _gmm_call(xe, w, group_sizes, interpret=pallas_interpret())
    return out[:, :C]


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def _paged_attention_call(q, k_pool, v_pool, block_tables, pos, window=0,
                          interpret=True):
    # lanes: hd -> 128; sublanes: G (q/out) and KV (pools) -> 8. The kernel
    # derives its softmax scale from the padded hd, so pre-scale q by
    # sqrt(hd_pad)/sqrt(hd) to cancel (zero-padded lanes add 0 to scores).
    KV, G, hd = q.shape[1:]
    q = _pad_to(_pad_to(_pad_to(q, 8, 1), 8, 2), 128, 3)
    k_pool = _pad_to(_pad_to(k_pool, 8, 2), 128, 3)
    v_pool = _pad_to(_pad_to(v_pool, 8, 2), 128, 3)
    hd_pad = q.shape[-1]
    if hd_pad != hd:
        q = q * jnp.asarray((hd_pad / hd) ** 0.5, q.dtype)
    out = _paged.paged_attention(q, k_pool, v_pool, block_tables, pos,
                                 window=window, interpret=interpret)
    return out[:, :KV, :G, :hd]


def paged_attention(q, k_pool, v_pool, block_tables, pos, *, window: int = 0):
    """Flash-decode attention over a paged KV pool.

    q: (B, KV, G, hd); k/v pool: (P, page_size, KV, hd); block_tables:
    (B, nb) int32; pos: (B,) int32 — see kernels/paged.py. -> (B,KV,G,hd) f32
    """
    if not kernels_enabled():
        return _ref.paged_attention_ref(q, k_pool, v_pool, block_tables,
                                        pos, window)
    return _paged_attention_call(q, k_pool, v_pool, block_tables, pos,
                                 window=window, interpret=pallas_interpret())


build_segments = _sgmv.build_segments
build_segments_ranked = _sgmv.build_segments_ranked
