"""Forward math for transformer layers (pure functions over param dicts).

Conventions:
  - activations: (B, S, d) residual stream; attention internals (B, S, H, hd)
  - params are plain dicts of jnp arrays; stacked-layer params carry a leading
    L dim and are consumed via lax.scan in transformer.py
  - sharding is annotated through repro.distributed.sharding.constrain and is
    a no-op without an active rules context (CPU smoke tests)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map

from repro.distributed.sharding import constrain, active_rules, mesh_axis_size

F32 = jnp.float32
NEG_INF = -1e30


def rms_norm(x, scale, eps: float = 1e-6):
    var = jnp.mean(jnp.square(x.astype(F32)), axis=-1, keepdims=True)
    out = x.astype(F32) * jax.lax.rsqrt(var + eps)
    return (out * (1.0 + scale.astype(F32))).astype(x.dtype)


# ------------------------------- RoPE ---------------------------------- #
def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (np.arange(0, head_dim, 2) / head_dim))


def apply_rope(x, positions, theta: float):
    """x: (B, S, H, hd); positions: (B, S) int32."""
    hd = x.shape[-1]
    freqs = jnp.asarray(rope_freqs(hd, theta), dtype=F32)  # (hd/2,)
    angles = positions[..., None].astype(F32) * freqs  # (B, S, hd/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(F32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------- projections ------------------------------ #
def qkv_project(x, p, cfg):
    """x: (B, S, d) -> q (B,S,H,hd), k/v (B,S,KV,hd), RoPE applied outside."""
    B, S, _ = x.shape
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"], preferred_element_type=F32)
    k = jnp.einsum("bsd,dh->bsh", x, p["wk"], preferred_element_type=F32)
    v = jnp.einsum("bsd,dh->bsh", x, p["wv"], preferred_element_type=F32)
    if cfg.qkv_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    q = q.astype(x.dtype).reshape(B, S, cfg.n_heads, cfg.head_dim)
    k = k.astype(x.dtype).reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    v = v.astype(x.dtype).reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    q = constrain(q, "batch", None, "heads", None)
    k = constrain(k, "batch", None, "kv_heads", None)
    v = constrain(v, "batch", None, "kv_heads", None)
    return q, k, v


def out_project(attn_out, p):
    """attn_out: (B, S, H, hd) -> (B, S, d)."""
    B, S = attn_out.shape[:2]
    flat = attn_out.reshape(B, S, -1)
    y = jnp.einsum("bsh,hd->bsd", flat, p["wo"], preferred_element_type=F32)
    return y.astype(attn_out.dtype)


# -------------------------- full attention ------------------------------ #
def _attn_mask(q_pos, k_pos, causal: bool, window: int):
    mask = jnp.ones((q_pos.shape[0], k_pos.shape[0]), dtype=bool)
    if causal:
        mask &= q_pos[:, None] >= k_pos[None, :]
    if window:
        mask &= (q_pos[:, None] - k_pos[None, :]) < window
    return mask


def _chunks(x, n, size):
    """(B, S, ...) -> (n, B, size, ...)."""
    B = x.shape[0]
    return jnp.moveaxis(x.reshape(B, n, size, *x.shape[2:]), 1, 0)


def _flash_fwd_impl(qg, k, v, causal, window, q_chunk, q_offset):
    B, Sq, KV, G, hd = qg.shape
    Sk = k.shape[1]
    scale = 1.0 / np.sqrt(hd)
    nc = Sq // q_chunk
    k_pos = jnp.arange(Sk)

    def chunk_fn(_, inp):
        ci, q_c = inp
        s = jnp.einsum("bqkgd,bskd->bkgqs", q_c, k,
                       preferred_element_type=F32) * scale
        q_pos = q_offset + ci * q_chunk + jnp.arange(q_chunk)
        mask = _attn_mask(q_pos, k_pos, causal, window)
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        m = jnp.max(s, axis=-1)
        p = jnp.exp(s - m[..., None])
        l = jnp.sum(p, axis=-1)
        o = jnp.einsum("bkgqs,bskd->bqkgd", p, v.astype(F32),
                       preferred_element_type=F32)
        o = o / jnp.maximum(l, 1e-20).transpose(0, 3, 1, 2)[..., None]
        lse = m + jnp.log(jnp.maximum(l, 1e-20))  # (B, KV, G, Cq)
        return None, (o.astype(qg.dtype), lse)

    qs = _chunks(qg, nc, q_chunk)
    _, (outs, lses) = jax.lax.scan(chunk_fn, None, (jnp.arange(nc), qs))
    out = jnp.moveaxis(outs, 0, 1).reshape(B, Sq, KV, G, hd)
    # lses: (nc, B, KV, G, Cq) -> (B, KV, G, Sq)
    lse = jnp.moveaxis(lses, 0, 3).reshape(B, KV, G, Sq)
    return out, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_attention(qg, k, v, causal, window, q_chunk, q_offset):
    out, _ = _flash_fwd_impl(qg, k, v, causal, window, q_chunk, q_offset)
    return out


def _flash_vjp_fwd(qg, k, v, causal, window, q_chunk, q_offset):
    out, lse = _flash_fwd_impl(qg, k, v, causal, window, q_chunk, q_offset)
    return out, (qg, k, v, out, lse)


def _flash_vjp_bwd(causal, window, q_chunk, q_offset, res, dout):
    """Flash-attention backward as TWO chunked scans with stacked outputs —
    no cross-iteration dk/dv accumulator. A scan-carried (B,S,KV,hd) f32
    accumulator reshards between seq- and head-layouts every iteration
    under sequence parallelism (measured ~9 gathers/layer on 72B train,
    EXPERIMENTS.md iteration 2); stacked ys keep one stable layout.
    """
    qg, k, v, out, lse = res
    B, Sq, KV, G, hd = qg.shape
    Sk = k.shape[1]
    scale = 1.0 / np.sqrt(hd)
    nc = Sq // q_chunk
    k_chunk = min(q_chunk, Sk)
    while Sk % k_chunk:
        k_chunk //= 2
    nk = Sk // k_chunk
    k_pos = jnp.arange(Sk)
    dout = dout.astype(F32)
    D = jnp.einsum("bqkgd,bqkgd->bkgq", dout, out.astype(F32))  # (B,KV,G,Sq)

    # pass 1: dq per q-chunk (touches all K; output stacked, no carry)
    def dq_chunk(_, inp):
        ci, q_c, do_c, lse_c, D_c = inp
        s = jnp.einsum("bqkgd,bskd->bkgqs", q_c, k,
                       preferred_element_type=F32) * scale
        q_pos = q_offset + ci * q_chunk + jnp.arange(q_chunk)
        mask = _attn_mask(q_pos, k_pos, causal, window)
        p = jnp.where(mask[None, None, None],
                      jnp.exp(s - lse_c[..., None]), 0.0)
        dp = jnp.einsum("bqkgd,bskd->bkgqs", do_c, v.astype(F32))
        ds = p * (dp - D_c[..., None]) * scale
        dq_c = jnp.einsum("bkgqs,bskd->bqkgd", ds, k.astype(F32))
        return None, dq_c

    qs = _chunks(qg, nc, q_chunk)
    dos = _chunks(dout, nc, q_chunk)
    lse_cs = jnp.moveaxis(lse.reshape(B, KV, G, nc, q_chunk), 3, 0)
    D_cs = jnp.moveaxis(D.reshape(B, KV, G, nc, q_chunk), 3, 0)
    _, dqs = jax.lax.scan(dq_chunk, None,
                          (jnp.arange(nc), qs, dos, lse_cs, D_cs))
    dq = jnp.moveaxis(dqs, 0, 1).reshape(B, Sq, KV, G, hd)

    # pass 2: dk/dv per K-chunk (touches all Q; stacked, no carry)
    q_pos_full = q_offset + jnp.arange(Sq)

    def dkv_chunk(_, inp):
        cj, k_c, v_c = inp
        s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k_c,
                       preferred_element_type=F32) * scale
        kp = cj * k_chunk + jnp.arange(k_chunk)
        mask = _attn_mask(q_pos_full, kp, causal, window)
        p = jnp.where(mask[None, None, None],
                      jnp.exp(s - lse[..., None]), 0.0)
        dv_c = jnp.einsum("bkgqs,bqkgd->bskd", p, dout)
        dp = jnp.einsum("bqkgd,bskd->bkgqs", dout, v_c.astype(F32))
        ds = p * (dp - D[..., None]) * scale
        dk_c = jnp.einsum("bkgqs,bqkgd->bskd", ds, qg.astype(F32))
        return None, (dk_c, dv_c)

    ks = _chunks(k, nk, k_chunk)
    vs = _chunks(v, nk, k_chunk)
    _, (dks, dvs) = jax.lax.scan(dkv_chunk, None, (jnp.arange(nk), ks, vs))
    dk = jnp.moveaxis(dks, 0, 1).reshape(B, Sk, KV, hd)
    dv = jnp.moveaxis(dvs, 0, 1).reshape(B, Sk, KV, hd)
    return dq.astype(qg.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_flash_attention.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def causal_attention(q, k, v, *, causal: bool = True, window: int = 0,
                     q_chunk: int = 512, q_offset: int = 0):
    """Chunked flash attention (custom VJP); never materializes the S x S
    scores in forward or backward.

    q: (B, Sq, H, hd); k, v: (B, Sk, KV, hd). GQA via head grouping.
    ``window`` > 0 masks keys older than ``window`` positions. ``q_offset``:
    absolute position of q[0] relative to k[0]. Returns (B, Sq, H, hd).

    Under active sharding rules with shardable heads, runs as an explicit
    shard_map over the model axis: q head-sharded, k/v replicated (gathered
    ONCE; their cotangent is psum'd once by the shard_map transpose). Under
    plain pjit the partitioner re-reshards the chunk loops' operands every
    iteration (measured 72 s -> 322 s of collectives on 72B train when the
    custom VJP landed without shard_map — EXPERIMENTS.md iteration 2).
    """
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    q_chunk = min(q_chunk, Sq)
    while Sq % q_chunk:
        q_chunk //= 2

    rules = active_rules()
    model_ax = None
    if rules is not None and Sq > 1:
        r = rules._resolve("heads", H)
        if r is not None:
            model_ax = r if isinstance(r, str) else r[0]
        batch_axes = rules.spec(["batch"], [B])[0]
        ba = (() if batch_axes is None else
              ((batch_axes,) if isinstance(batch_axes, str) else batch_axes))
        if model_ax in ba:
            model_ax = None  # batch already consumes the model axis

    if model_ax is None:
        qg = q.reshape(B, Sq, KV, G, hd)
        out = _flash_attention(qg, k, v, causal, window, q_chunk,
                               int(q_offset))
        return out.reshape(B, Sq, H, hd)

    from jax.sharding import PartitionSpec as P

    n_model = mesh_axis_size(model_ax)
    H_loc = H // n_model
    batch_axes = rules.spec(["batch"], [B])[0]
    q_spec = P(batch_axes, None, model_ax, None)
    kv_spec = P(batch_axes, None, None, None)  # replicated over model

    def local(qh, kh, vh):
        # qh: (B_l, Sq, H_loc, hd); kh/vh: (B_l, Sk, KV, hd) full kv heads.
        # expand kv per local head (GQA indexing is global-head // G)
        rank = jax.lax.axis_index(model_ax)
        head0 = rank * H_loc
        kv_idx = (head0 + jnp.arange(H_loc)) // G
        k_sel = jnp.take(kh, kv_idx, axis=2)
        v_sel = jnp.take(vh, kv_idx, axis=2)
        qg_l = qh.reshape(qh.shape[0], Sq, H_loc, 1, hd)
        out = _flash_attention(qg_l, k_sel, v_sel, causal, window, q_chunk,
                               int(q_offset))
        return out.reshape(qh.shape[0], Sq, H_loc, hd)

    fn = shard_map(local, mesh=rules.mesh, in_specs=(q_spec, kv_spec, kv_spec),
                   out_specs=q_spec, check_vma=False)
    return fn(q, k, v)


# -------------------------- decode attention ---------------------------- #
def _local_decode_scores(q, k, v, key_positions, pos, window, k_scale=None,
                         v_scale=None):
    """Partial (pre-softmax-combine) decode attention over a KV slice.

    q: (B, KV, G, hd); k/v: (B, S_loc, KV, hd); key_positions: (S_loc,) global.
    Returns (m, l, o): running max (B,KV,G), sum-exp (B,KV,G),
    weighted values (B,KV,G,hd) — combinable with the LSE trick.
    """
    if k_scale is not None:  # int8-quantized KV cache
        k = k.astype(F32) * k_scale
        v = v.astype(F32) * v_scale
    hd = q.shape[-1]
    scale = 1.0 / np.sqrt(hd)
    scores = jnp.einsum("bkgd,bskd->bkgs", q.astype(F32), k.astype(F32)) * scale
    if jnp.ndim(pos) == 0:  # one shared position (static batch)
        valid = (key_positions >= 0) & (key_positions < pos)
        if window:
            valid &= key_positions >= pos - window
        vmask = valid[None, None, None, :]
    else:  # per-row positions (continuous batching: pos is (B,))
        kp = key_positions[None, :]
        valid = (kp >= 0) & (kp < pos[:, None])
        if window:
            valid &= kp >= pos[:, None] - window
        vmask = valid[:, None, None, :]
    scores = jnp.where(vmask, scores, NEG_INF)
    m = jnp.max(scores, axis=-1)
    e = jnp.exp(scores - m[..., None])
    l = jnp.sum(e, axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", e, v.astype(F32))
    return m, l, o


def _write_local(buf, new, local_idx, in_range):
    """Write one token (B, KV, hd|1) at a LOCAL seq index into (B, S_loc,
    KV, ...), masked by ownership — a plain in-place DUS on the local shard.
    """
    idx_c = jnp.clip(local_idx, 0, buf.shape[1] - 1)
    cur = jax.lax.dynamic_slice_in_dim(buf, idx_c, 1, axis=1)
    upd = jnp.where(in_range, new[:, None].astype(buf.dtype), cur)
    return jax.lax.dynamic_update_slice_in_dim(buf, upd, idx_c, axis=1)


def decode_attention_update(q, k_new, v_new, k_cache, v_cache, pos, *,
                            window: int = 0, k_scale=None, v_scale=None,
                            key_positions=None, write_slot=None):
    """Fused KV-write + single-token flash-decode attention.

    q: (B, H, hd); k_new/v_new: (B, KV, hd) this token's K/V (post-RoPE);
    k_cache/v_cache: (B, S, KV, hd) [+ (B, S, KV, 1) scales for int8];
    pos: tokens already cached (this token becomes position ``pos``);
    write_slot: cache slot for the new token (default pos; ring buffers pass
    pos % W); key_positions: (S,) absolute position per slot (ring), updated
    with the write and returned.

    The write happens INSIDE the seq-sharded shard_map so it is a local DUS
    on the owning shard — a top-level DUS on a sharded dim lowers to a
    full-cache masked select (measured 4x cache footprint on 72B decode).

    Returns (out (B, H, hd), k_cache', v_cache', k_scale', v_scale',
             key_positions').
    """
    B, H, hd = q.shape
    KV = k_cache.shape[2]
    G = H // KV
    qg = q.reshape(B, KV, G, hd)
    S = k_cache.shape[1]
    slot = pos if write_slot is None else write_slot
    quant = k_scale is not None
    has_kp = key_positions is not None

    rules = active_rules()
    axis = None
    if rules is not None:
        resolved = rules._resolve("kv_seq", S)
        if resolved is not None:
            axis = resolved if isinstance(resolved, str) else resolved[0]
    n_shards = mesh_axis_size(axis) if axis else 1
    S_loc = S // n_shards

    def local_body(qg_l, kn, vn, k_l, v_l, kp_l, ks_l, vs_l, pos_l, slot_l,
                   shard_idx):
        start = shard_idx * S_loc
        local_idx = slot_l - start
        own = (local_idx >= 0) & (local_idx < S_loc)
        if quant:
            knq, kns = quantize_kv_token(kn)
            vnq, vns = quantize_kv_token(vn)
            k_l = _write_local(k_l, knq, local_idx, own)
            v_l = _write_local(v_l, vnq, local_idx, own)
            ks_l = _write_local(ks_l, kns, local_idx, own)
            vs_l = _write_local(vs_l, vns, local_idx, own)
        else:
            k_l = _write_local(k_l, kn, local_idx, own)
            v_l = _write_local(v_l, vn, local_idx, own)
        if has_kp:
            cur = jax.lax.dynamic_slice_in_dim(
                kp_l, jnp.clip(local_idx, 0, S_loc - 1), 1)
            kp_l = jax.lax.dynamic_update_slice_in_dim(
                kp_l, jnp.where(own, pos_l, cur[0])[None],
                jnp.clip(local_idx, 0, S_loc - 1), 0)
            kp_use = kp_l
        else:
            kp_use = start + jnp.arange(S_loc, dtype=jnp.int32)
        m, l, o = _local_decode_scores(qg_l, k_l, v_l, kp_use, pos_l + 1,
                                       window, ks_l, vs_l)
        return m, l, o, k_l, v_l, kp_l, ks_l, vs_l

    if axis is None:
        m, l, o, k_c, v_c, kp, ks, vs = local_body(
            qg, k_new, v_new, k_cache, v_cache, key_positions, k_scale,
            v_scale, pos, slot, 0)
        out = o / jnp.maximum(l, 1e-20)[..., None]
        return (out.reshape(B, H, hd).astype(q.dtype), k_c, v_c, ks, vs, kp)

    from jax.sharding import PartitionSpec as P

    batch_axes = rules.spec(["batch"], [B])[0]
    kv_spec = P(batch_axes, axis, None, None)
    tok_spec = P(batch_axes, None, None)
    q_spec = P(batch_axes, None, None, None)
    sc_spec = P(batch_axes, axis, None, None) if quant else None
    kp_spec = P(axis) if has_kp else None

    def sm_body(qg_l, kn, vn, k_l, v_l, kp_l, ks_l, vs_l, pos_l, slot_l):
        m, l, o, k_l, v_l, kp_l, ks_l, vs_l = local_body(
            qg_l, kn, vn, k_l, v_l, kp_l, ks_l, vs_l, pos_l, slot_l,
            jax.lax.axis_index(axis))
        M = jax.lax.pmax(m, axis)
        corr = jnp.exp(m - M)
        l_tot = jax.lax.psum(l * corr, axis)
        o_tot = jax.lax.psum(o * corr[..., None], axis)
        out = o_tot / jnp.maximum(l_tot, 1e-20)[..., None]
        return out, k_l, v_l, kp_l, ks_l, vs_l

    fn = shard_map(
        sm_body, mesh=rules.mesh,
        in_specs=(q_spec, tok_spec, tok_spec, kv_spec, kv_spec, kp_spec,
                  sc_spec, sc_spec, P(), P()),
        out_specs=(q_spec, kv_spec, kv_spec, kp_spec, sc_spec, sc_spec),
        check_vma=False)
    out, k_c, v_c, kp, ks, vs = fn(qg, k_new, v_new, k_cache, v_cache,
                                   key_positions, k_scale, v_scale, pos, slot)
    return (out.reshape(B, H, hd).astype(q.dtype), k_c, v_c, ks, vs, kp)


def decode_attention_update_slots(q, k_new, v_new, k_cache, v_cache, pos_vec,
                                  *, window: int = 0):
    """Per-slot KV-write + flash-decode attention for continuous batching.

    Each batch row is an engine slot with its OWN sequence length: this
    token's write position and the valid-key mask differ per row, unlike
    ``decode_attention_update`` where one scalar ``pos`` covers the batch.

    q: (B, H, hd); k_new/v_new: (B, KV, hd) post-RoPE; k_cache/v_cache:
    (B, S, KV, hd); pos_vec: (B,) int32 tokens already cached per row.
    Rows with pos_vec < 0 are INACTIVE slots: their cache rows are left
    untouched and their output is ignorable garbage (finite, never NaN).

    Single-shard only — the slot engine runs one instance per host; the
    sharded static-batch variant stays ``decode_attention_update``.

    Returns (out (B, H, hd), k_cache', v_cache').
    """
    B, H, hd = q.shape
    KV = k_cache.shape[2]
    G = H // KV
    S = k_cache.shape[1]
    active = pos_vec >= 0
    idx = jnp.clip(pos_vec, 0, S - 1)
    bidx = jnp.arange(B)
    # masked per-row scatter write: inactive rows rewrite their old value
    k_row = jnp.where(active[:, None, None], k_new.astype(k_cache.dtype),
                      k_cache[bidx, idx])
    v_row = jnp.where(active[:, None, None], v_new.astype(v_cache.dtype),
                      v_cache[bidx, idx])
    k_cache = k_cache.at[bidx, idx].set(k_row)
    v_cache = v_cache.at[bidx, idx].set(v_row)
    qg = q.reshape(B, KV, G, hd)
    kp = jnp.arange(S, dtype=jnp.int32)
    m, l, o = _local_decode_scores(qg, k_cache, v_cache, kp, pos_vec + 1,
                                   window)
    out = o / jnp.maximum(l, 1e-20)[..., None]
    return out.reshape(B, H, hd).astype(q.dtype), k_cache, v_cache


def decode_attention_update_slots_paged(q, k_new, v_new, k_pool, v_pool,
                                        block_table, pos_vec, *,
                                        window: int = 0):
    """Per-slot KV-write + flash-decode attention over a PAGED block pool.

    The paged twin of ``decode_attention_update_slots``: instead of each row
    owning a contiguous (S, KV, hd) cache strip, rows own block tables into
    a shared (P, page_size, KV, hd) pool, so KV memory is bounded by actual
    token residency rather than n_slots x max_len.

    q: (B, H, hd); k_new/v_new: (B, KV, hd) post-RoPE; k_pool/v_pool:
    (P, page_size, KV, hd); block_table: (B, nb) int32 page ids (-1 =
    unallocated); pos_vec: (B,) int32 tokens already cached per row. The
    caller (engine) guarantees the page covering position pos_vec[b] is
    allocated for every active row. Rows with pos_vec < 0 are inactive:
    no write, finite garbage output. Pages are slot-exclusive, so distinct
    active rows can never scatter to the same (page, offset) cell.

    Returns (out (B, H, hd), k_pool', v_pool').
    """
    B, H, hd = q.shape
    KV = k_pool.shape[2]
    G = H // KV
    P, ps = k_pool.shape[:2]
    bidx = jnp.arange(B)
    posc = jnp.maximum(pos_vec, 0)
    page = block_table[bidx, posc // ps]
    # inactive rows and unallocated pages scatter out of bounds -> dropped
    page = jnp.where((pos_vec >= 0) & (page >= 0), page, P)
    off = posc % ps
    k_pool = k_pool.at[page, off].set(k_new.astype(k_pool.dtype),
                                      mode="drop")
    v_pool = v_pool.at[page, off].set(v_new.astype(v_pool.dtype),
                                      mode="drop")
    from repro.kernels import ops
    out = ops.paged_attention(q.reshape(B, KV, G, hd), k_pool, v_pool,
                              block_table, pos_vec, window=window)
    return out.reshape(B, H, hd).astype(q.dtype), k_pool, v_pool


def quantize_kv_token(x):
    """x: (B, KV, hd) -> (int8, scale (B, KV, 1))."""
    amax = jnp.max(jnp.abs(x.astype(F32)), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-6) / 127.0
    q = jnp.clip(jnp.round(x.astype(F32) / scale), -127, 127).astype(jnp.int8)
    return q, scale


def decode_attention(q, k_cache, v_cache, pos, *, window: int = 0,
                     kv_scales=None, key_positions=None):
    """Read-only single-token attention over an existing cache (cross
    attention and tests). Same LSE-combined flash-decode as
    decode_attention_update, without the write."""
    B, H, hd = q.shape
    KV = k_cache.shape[2]
    G = H // KV
    qg = q.reshape(B, KV, G, hd)
    S = k_cache.shape[1]
    k_scale = v_scale = None
    if kv_scales is not None:
        k_scale, v_scale = kv_scales
    rules = active_rules()
    axis = None
    if rules is not None:
        resolved = rules._resolve("kv_seq", S)
        if resolved is not None:
            axis = resolved if isinstance(resolved, str) else resolved[0]

    if axis is None:
        kp = (key_positions if key_positions is not None
              else jnp.arange(S, dtype=jnp.int32))
        m, l, o = _local_decode_scores(qg, k_cache, v_cache, kp, pos,
                                       window, k_scale, v_scale)
        out = o / jnp.maximum(l, 1e-20)[..., None]
        return out.reshape(B, H, hd).astype(q.dtype)

    from jax.sharding import PartitionSpec as P

    n_shards = mesh_axis_size(axis)
    S_loc = S // n_shards
    batch_axes = rules.spec(["batch"], [B])[0]
    kv_spec = P(batch_axes, axis, None, None)
    q_spec = P(batch_axes, None, None, None)
    sc_spec = P(batch_axes, axis, None, None) if k_scale is not None else None
    kp_spec = P(axis) if key_positions is not None else None

    def local(qg_l, k_l, v_l, kp_l, pos_l, ks_l, vs_l):
        if kp_l is None:
            kp_l = (jax.lax.axis_index(axis) * S_loc
                    + jnp.arange(S_loc, dtype=jnp.int32))
        m, l, o = _local_decode_scores(qg_l, k_l, v_l, kp_l, pos_l,
                                       window, ks_l, vs_l)
        M = jax.lax.pmax(m, axis)
        corr = jnp.exp(m - M)
        l_tot = jax.lax.psum(l * corr, axis)
        o_tot = jax.lax.psum(o * corr[..., None], axis)
        return o_tot / jnp.maximum(l_tot, 1e-20)[..., None]

    fn = shard_map(local, mesh=rules.mesh,
                   in_specs=(q_spec, kv_spec, kv_spec, kp_spec, P(),
                             sc_spec, sc_spec),
                   out_specs=q_spec, check_vma=False)
    out = fn(qg, k_cache, v_cache, key_positions, pos, k_scale, v_scale)
    return out.reshape(B, H, hd).astype(q.dtype)


# ------------------------------- MLP ------------------------------------ #
def _mlp_math(x, p, cfg, gate_w, up_w, down_w, inside_sm=False):
    if cfg.gated_mlp:
        g = jnp.einsum("bsd,df->bsf", x, gate_w, preferred_element_type=F32)
        u = jnp.einsum("bsd,df->bsf", x, up_w, preferred_element_type=F32)
        h = (jax.nn.silu(g) * u).astype(x.dtype)
    else:
        u = jnp.einsum("bsd,df->bsf", x, up_w, preferred_element_type=F32)
        h = jax.nn.gelu(u).astype(x.dtype)
    if not inside_sm:  # sharding constraints are illegal on manual axes
        h = constrain(h, "batch", None, "mlp")
    return jnp.einsum("bsf,fd->bsd", h, down_w, preferred_element_type=F32)


def mlp(x, p, cfg):
    """SwiGLU (gated) or classic GELU MLP. x: (B, S, d).

    Under sequence parallelism runs as an explicit shard_map with the
    Megatron-SP primitive pair — all_gather(x) forward, psum_scatter(y)
    back to seq-sharded — which guarantees reduce-scatter cotangents; the
    pjit partitioner was emitting full all-reduces of the (B,S,d) residual
    cotangent instead (EXPERIMENTS.md §Perf iteration 5).
    """
    B, S, d = x.shape
    ff = p["down"].shape[-2] if p["down"].ndim >= 2 else cfg.d_ff
    rules = active_rules()
    seq_ax = None
    if rules is not None and S > 1:
        r = rules._resolve("seq", S)
        seq_ax = (r if isinstance(r, str) else r[0]) if r is not None else None
        rf = rules._resolve("mlp", ff)
        ff_ax = (rf if isinstance(rf, str) else rf[0]) if rf is not None else None
        if seq_ax is None or ff_ax != seq_ax:
            seq_ax = None
    if seq_ax is None:
        return _mlp_math(x, p, cfg, p.get("gate"), p["up"],
                         p["down"]).astype(x.dtype)

    from jax.sharding import PartitionSpec as P

    # weight at-rest specs (dim0/dim1 per param_specs: fsdp x model)
    def wspec(name, dim_ff):
        axes = ("fsdp", "mlp") if dim_ff == 1 else ("mlp", "fsdp")
        return rules.spec(axes, p[name].shape)

    fsdp_g = rules._resolve("fsdp", d)
    fsdp_g = (fsdp_g if isinstance(fsdp_g, str) else fsdp_g[0])         if fsdp_g is not None else None
    batch_axes = rules.spec(["batch"], [B])[0]
    x_spec = P(batch_axes, seq_ax, None)

    gated = cfg.gated_mlp

    def body(x_l, up_l, down_l, gate_l):
        if fsdp_g is not None:  # ZeRO-3: reassemble this layer's dim-0/1
            up_l = jax.lax.all_gather(up_l, fsdp_g, axis=0, tiled=True)
            down_l = jax.lax.all_gather(down_l, fsdp_g, axis=1, tiled=True)
            if gated:
                gate_l = jax.lax.all_gather(gate_l, fsdp_g, axis=0, tiled=True)
        xg = jax.lax.all_gather(x_l, seq_ax, axis=1, tiled=True)  # (B_l,S,d)
        y = _mlp_math(xg, p, cfg, gate_l, up_l, down_l,
                      inside_sm=True)  # partial over ff
        return jax.lax.psum_scatter(y, seq_ax, scatter_dimension=1,
                                    tiled=True).astype(x_l.dtype)

    gate = p["gate"] if gated else p["up"]
    fn = shard_map(
        body, mesh=rules.mesh,
        in_specs=(x_spec, wspec("up", 1), wspec("down", 0),
                  wspec("gate", 1) if gated else wspec("up", 1)),
        out_specs=x_spec, check_vma=False)
    return fn(x, p["up"], p["down"], gate)


# ---------------------------- embeddings -------------------------------- #
def embed(tokens, table):
    """tokens: (B, S) int32; table: (V, d)."""
    return jnp.take(table, tokens, axis=0)


def unembed(x, table):
    """x: (B, S, d) -> logits (B, S, V) with vocab sharded."""
    logits = jnp.einsum("bsd,vd->bsv", x, table, preferred_element_type=F32)
    return constrain(logits, "batch", None, "vocab")
