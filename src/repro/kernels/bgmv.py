"""BGMV Pallas TPU kernel — batched gather matvec for decode-time LoRA.

GPU original (paper §5.2): thread-collaborative gather + GEMV per token,
since wgmma pipelines don't pay off at batch-1-per-adapter intensity.

TPU adaptation (DESIGN.md §3): the gather moves to the *grid index map* —
scalar-prefetched adapter ids steer each grid step's BlockSpec so Mosaic's
pipeline emitter DMAs exactly one adapter's A/B tile from HBM to VMEM per
token (the TMA+warp-specialization analogue: double-buffered DMA overlaps
the previous token's VPU/MXU work). Rows with id < 0 write zeros.

  x: (T, d_in) ; A: (N, d_in, r) ; B: (N, r, d_out) ; ids: (T,) int32
  -> (T, d_out) f32

Expert variant (MoE expert-specific adapters, paper Fig. 3b):
  A: (N, E, d_in, r) ; B: (N, E, r, d_out) ; eids: (T,) expert per row.

VMEM budget per grid step: d_in*r + r*d_out + d_in + d_out floats — e.g.
d=8192, r=64, d_out=8192: ~2.2 MB in bf16, well under the ~16 MB/core VMEM;
block dims are 128-lane aligned via the ops.py padding wrapper. Rows are
laid out as (T, 1, d) so each grid step's x/out block is a whole (1, d)
trailing slab: Mosaic requires a block's last two dims to be (8, 128)
multiples or the array's own, and a (1, d) block of a (T, d) array is
neither.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32


def _kernel(ids_ref, x_ref, a_ref, b_ref, o_ref):
    i = pl.program_id(0)

    @pl.when(ids_ref[i] >= 0)
    def _():
        h = jnp.dot(x_ref[0].astype(F32), a_ref[0].astype(F32),
                    preferred_element_type=F32)          # (1, r)
        o_ref[0] = jnp.dot(h, b_ref[0].astype(F32),
                           preferred_element_type=F32)  # (1, d_out)

    @pl.when(ids_ref[i] < 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)


def bgmv(x, A, B, ids, *, interpret: bool = True):
    """See module docstring. Shapes must be lane-aligned (ops.py pads)."""
    T, d_in = x.shape
    N, _, r = A.shape
    d_out = B.shape[-1]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(T,),
        in_specs=[
            pl.BlockSpec((1, 1, d_in), lambda i, ids: (i, 0, 0)),
            pl.BlockSpec((1, d_in, r),
                         lambda i, ids: (jnp.maximum(ids[i], 0), 0, 0)),
            pl.BlockSpec((1, r, d_out),
                         lambda i, ids: (jnp.maximum(ids[i], 0), 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, d_out), lambda i, ids: (i, 0, 0)),
    )
    out = pl.pallas_call(
        _kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, 1, d_out), F32),
        interpret=interpret,
    )(ids.astype(jnp.int32), x.reshape(T, 1, d_in), A, B)
    return out.reshape(T, d_out)


def _kernel_ranked(ids_ref, ranks_ref, x_ref, a_ref, b_ref, o_ref):
    i = pl.program_id(0)

    @pl.when(ids_ref[i] >= 0)
    def _():
        h = jnp.dot(x_ref[0].astype(F32), a_ref[0].astype(F32),
                    preferred_element_type=F32)          # (1, r)
        # per-slot true rank: lanes past it are the pool's exact-zero
        # padding — force +0.0 so trimming stays bit-compatible
        col = jax.lax.broadcasted_iota(jnp.int32, h.shape, 1)
        h = jnp.where(col < ranks_ref[i], h, 0.0)
        o_ref[0] = jnp.dot(h, b_ref[0].astype(F32),
                           preferred_element_type=F32)  # (1, d_out)

    @pl.when(ids_ref[i] < 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)


def bgmv_ranked(x, A, B, ids, ranks, *, interpret: bool = True):
    """``bgmv`` with a per-slot true rank: ``ranks`` is (N,) per-adapter —
    each row's contraction is bounded at its adapter's true rank instead of
    the pool rank."""
    T, d_in = x.shape
    N, _, r = A.shape
    d_out = B.shape[-1]
    ranks = jnp.asarray(ranks, jnp.int32)
    row_ranks = jnp.where(ids >= 0, ranks[jnp.clip(ids, 0, N - 1)], 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(T,),
        in_specs=[
            pl.BlockSpec((1, 1, d_in), lambda i, ids, ranks: (i, 0, 0)),
            pl.BlockSpec((1, d_in, r),
                         lambda i, ids, ranks: (jnp.maximum(ids[i], 0),
                                                0, 0)),
            pl.BlockSpec((1, r, d_out),
                         lambda i, ids, ranks: (jnp.maximum(ids[i], 0),
                                                0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, d_out),
                               lambda i, ids, ranks: (i, 0, 0)),
    )
    out = pl.pallas_call(
        _kernel_ranked, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, 1, d_out), F32),
        interpret=interpret,
    )(ids.astype(jnp.int32), row_ranks.astype(jnp.int32),
      x.reshape(T, 1, d_in), A, B)
    return out.reshape(T, d_out)


def _kernel_expert(ids_ref, eids_ref, x_ref, a_ref, b_ref, o_ref):
    i = pl.program_id(0)

    @pl.when(ids_ref[i] >= 0)
    def _():
        h = jnp.dot(x_ref[0].astype(F32), a_ref[0, 0].astype(F32),
                    preferred_element_type=F32)
        o_ref[0] = jnp.dot(h, b_ref[0, 0].astype(F32),
                           preferred_element_type=F32)

    @pl.when(ids_ref[i] < 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)


def bgmv_expert(x, A, B, ids, eids, *, interpret: bool = True):
    T, d_in = x.shape
    N, E, _, r = A.shape
    d_out = B.shape[-1]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(T,),
        in_specs=[
            pl.BlockSpec((1, 1, d_in), lambda i, ids, eids: (i, 0, 0)),
            pl.BlockSpec(
                (1, 1, d_in, r),
                lambda i, ids, eids: (jnp.maximum(ids[i], 0), eids[i], 0, 0)),
            pl.BlockSpec(
                (1, 1, r, d_out),
                lambda i, ids, eids: (jnp.maximum(ids[i], 0), eids[i], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, d_out), lambda i, ids, eids: (i, 0, 0)),
    )
    out = pl.pallas_call(
        _kernel_expert, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, 1, d_out), F32),
        interpret=interpret,
    )(ids.astype(jnp.int32), eids.astype(jnp.int32), x.reshape(T, 1, d_in),
      A, B)
    return out.reshape(T, d_out)
