"""Client-side disaggregated LoRA execution (paper §3 / Fig. 7).

The LLM instance stays LoRA-free; at each MoE layer's two hook points the
activated (token, expert) rows are shipped to the LoRA Server and the deltas
are added to the locally computed base GEMM outputs:

    g, u  = x W_g, x W_u                       (client, overlapped with ...)
    dg,du = server.compute("up",   l, x-rows)  (... this transfer+compute)
    h     = silu(g + dg) * (u + du)
    y     = h W_d + server.compute("down", l, h-rows)

This module is the *functional* data path (used by the CPU demo and the
equivalence tests: disaggregated == coupled bit-for-bit). Wall-clock behavior
under load (overlap, queueing, SLOs) is the simulator's job — the paper's own
evaluation quantity.

``server`` only needs the ``compute(hook, layer, rows, adapter_ids,
expert_ids)`` contract, which is how ONE hook body serves BOTH transport
planes (src/repro/transport/): under ``HostTransport`` it is a real
``LoRAServer``/``ServerPool`` and the per-layer Python loop is the honest
structure of the host-mediated round trip (each call an async DMA + remote
dispatch on real hardware); under ``FusedTransport`` it is a traced
``DeviceLoraView`` and the same loop unrolls into one jitted program with
zero host round trips — sharing the body is what guarantees the two planes
cannot diverge by a token.

Two decode steps share one per-layer MoE hook body (``_moe_hooks_layer``):
``disagg_decode_step`` (static batch, scalar position — the legacy engine
API) and ``disagg_decode_step_slots`` (continuous batching, per-slot
positions — the slot engine). Keeping the hook math in one place is what
guarantees both stay token-identical to the coupled path.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from jax import shard_map
from repro.configs.base import ModelConfig
from repro.models import layers as ll
from repro.models import moe as moe_mod
from repro.core.lora_server import LoRAServer

F32 = jnp.float32


def _layer_params(params, l):
    return jax.tree_util.tree_map(lambda a: a[l], params["layers"])


# ------------------- expert-parallel base GEMMs (mesh plane) ------------- #
# The expert GEMMs are independent per expert (E is a batch dim), so
# sharding them over the mesh's expert axis is a pure map: shard_map with
# matching in/out specs and NO collectives. Each expert's (C,d)x(d,f) GEMM
# is then the exact same XLA routine as the unsharded run, which is what
# keeps the mesh plane token-stream BIT-identical to the single-device
# plane (the serving invariant). Contrast the coupled plane's allgather
# MoE, whose psum reassociates floats — that is why the mesh knob is only
# offered on the disaggregated planes.
_EP_EINSUM_CACHE: Dict = {}


def _ep_einsum(eq: str, a, w, mesh_ctx):
    """``jnp.einsum(eq, a, w)`` with both operands' leading expert dim
    mapped over ``mesh_ctx.axis``; plain einsum when there is no ctx or E
    does not divide the axis."""
    if mesh_ctx is None or mesh_ctx.size <= 1 or \
            a.shape[0] % mesh_ctx.size != 0 or \
            w.shape[0] % mesh_ctx.size != 0:
        return jnp.einsum(eq, a, w, preferred_element_type=F32)
    key = (eq, mesh_ctx.mesh, mesh_ctx.axis)
    mapped = _EP_EINSUM_CACHE.get(key)
    if mapped is None:
        spec = P(mesh_ctx.axis)

        def body(ai, wi):
            return jnp.einsum(eq, ai, wi, preferred_element_type=F32)

        mapped = jax.jit(shard_map(body, mesh=mesh_ctx.mesh,
                                   in_specs=(spec, spec), out_specs=spec,
                                   check_vma=False))
        _EP_EINSUM_CACHE[key] = mapped
    if isinstance(a, jax.core.Tracer) or isinstance(w, jax.core.Tracer):
        return mapped(a, w)
    # eager (host-plane) call: commit the operands to the mesh layout the
    # map expects, and hand back a fully-replicated result so downstream
    # eager ops never mix device assignments
    sh = NamedSharding(mesh_ctx.mesh, P(mesh_ctx.axis))
    # staticcheck: disable=SC006 (tracer-guarded eager branch, host plane)
    out = mapped(jax.device_put(a, sh), jax.device_put(w, sh))
    # staticcheck: disable=SC006 (tracer-guarded eager branch, host plane)
    return jax.device_put(out, NamedSharding(mesh_ctx.mesh, P()))


_PAGED_ATTN_CACHE: Dict = {}


def _paged_attention(q, k_new, v_new, k_pool, v_pool, block_table, pos_vec,
                     window: int, mesh_ctx):
    """``ll.decode_attention_update_slots_paged``; under a mesh it runs as
    one replicated copy per device inside a shard_map, because XLA cannot
    partition a Mosaic kernel (the paged-attention kernel) by itself. The
    map has no collectives, and each device computes exactly the
    single-device program."""
    if mesh_ctx is None:
        return ll.decode_attention_update_slots_paged(
            q, k_new, v_new, k_pool, v_pool, block_table, pos_vec,
            window=window)
    key = (mesh_ctx.mesh, window)
    mapped = _PAGED_ATTN_CACHE.get(key)
    if mapped is None:
        def body(*args):
            return ll.decode_attention_update_slots_paged(*args,
                                                          window=window)

        mapped = jax.jit(shard_map(body, mesh=mesh_ctx.mesh, in_specs=P(),
                                   out_specs=P(), check_vma=False))
        _PAGED_ATTN_CACHE[key] = mapped
    return mapped(q, k_new, v_new, k_pool, v_pool, block_table, pos_vec)


def _client_attn(x, lp, cfg, pos, k_c, v_c, positions):
    h = ll.rms_norm(x, lp["ln1"], cfg.norm_eps)
    q, k, v = ll.qkv_project(h, lp["attn"], cfg)
    q = ll.apply_rope(q, positions, cfg.rope_theta)
    k = ll.apply_rope(k, positions, cfg.rope_theta)
    att, k_c, v_c, _, _, _ = ll.decode_attention_update(
        q[:, 0], k[:, 0], v[:, 0], k_c, v_c, pos, window=cfg.sliding_window)
    x = x + ll.out_project(att[:, None], lp["attn"])
    return x, k_c, v_c


def _replicate_eager(d, mesh_ctx):
    """Eager-path helper: commit a hook delta onto the mesh (replicated) so
    the residual add never mixes device assignments. No-op under a trace
    and without a mesh."""
    if mesh_ctx is None or isinstance(d, jax.core.Tracer):
        return d
    # staticcheck: disable=SC006 (tracer-guarded eager branch, host plane)
    return jax.device_put(d, NamedSharding(mesh_ctx.mesh, P()))


def _capacity(cfg: ModelConfig, T: int) -> int:
    """Per-expert dispatch slots for ``T`` decode tokens: the same dropless
    threshold as the coupled path (_moe_local). The two paths must drop (or
    not drop) identically at EVERY batch size, else the coupled==disagg
    token equality breaks on huge decode buckets."""
    K = cfg.top_k
    return moe_mod.capacity(T, K, cfg.n_experts, cfg.capacity_factor,
                            dropless=(T * K <= 4096))


def hook_rows(cfg: ModelConfig, T: int) -> int:
    """Rows one hook call computes for ``T`` decode tokens: the most live
    (token, expert) pairs the ``E x C`` dispatch buffer can hold,
    ``min(E*C, T*top_k)``. Dropless decode has ``C >= T*top_k``, so this
    is ``T*top_k``: one row in ``E`` of the buffer."""
    return min(cfg.n_experts * _capacity(cfg, T), T * cfg.top_k)


def _live_pairs(slot_tok, adapter_ids, T: int, P: int):
    """The live (token, expert) pairs of a dispatch buffer, in slot order,
    padded to the static length ``P``: each pair's dispatch-slot index
    (padding: one past the buffer), a clamped copy to gather with, and its
    adapter id (padding: -1, so its delta is exact 0.0)."""
    n = slot_tok.shape[0]
    (pair,) = jnp.nonzero(slot_tok < T, size=P, fill_value=n)
    pair_slot = jnp.minimum(pair, n - 1)
    tok = jnp.minimum(slot_tok[pair_slot], T - 1)
    pair_adapter = jnp.where(pair < n, jnp.asarray(adapter_ids)[tok], -1)
    return pair, pair_slot, pair_adapter


def _hook_delta(server, hook: str, l: int, rows, pairs, C: int,
                mesh_ctx=None):
    """The server's delta for dispatch rows (E*C, d_in) -> (E*C, d_out),
    computed on the live pairs only; every other row is exact 0.0, as the
    server gives a row of adapter -1."""
    pair, pair_slot, pair_adapter = pairs
    d = server.compute(hook, l, rows[pair_slot], pair_adapter,
                       pair_slot // C)
    d = _replicate_eager(d, mesh_ctx)
    return jnp.zeros((rows.shape[0], d.shape[-1]), d.dtype).at[pair].set(
        d, mode="drop")


def _moe_hooks_layer(x, lp, cfg: ModelConfig, l: int, server: LoRAServer,
                     adapter_ids, lora_scale: float, mesh_ctx=None):
    """One MoE layer with the two server hook points (paper Fig. 7b): base
    GEMMs on the client, LoRA deltas from the remote server, router-weight
    combine. x: (B, 1, d) post-attention residual; adapter_ids: (B,) global
    ids (-1 rows get zero delta). Shared by BOTH decode-step variants so the
    hook math cannot diverge between them. With ``mesh_ctx`` the three base
    expert GEMMs run expert-parallel over the mesh (see ``_ep_einsum``).
    The server computes ``hook_rows`` rows per hook, the live pairs, not
    the ``E x C`` dispatch buffer."""
    B = x.shape[0]
    E, K = cfg.n_experts, cfg.top_k
    with jax.named_scope("moe_router"):
        h = ll.rms_norm(x, lp["ln2"], cfg.norm_eps)
        xf = h.reshape(-1, cfg.d_model)
        T = xf.shape[0]
        ids, wts = moe_mod.route(xf, lp["moe"]["router"], E, K)
        C = _capacity(cfg, T)
        xe, slot_tok = moe_mod.local_dispatch(xf, ids, C, E)  # (E, C, d)
        tok_safe = jnp.minimum(slot_tok, T - 1)
        pairs = _live_pairs(slot_tok, adapter_ids, T, hook_rows(cfg, T))

    # hook 1: up/gate — client GEMM + server delta (overlapped on HW)
    mp = lp["moe"]
    with jax.named_scope("moe_experts"):
        g = _ep_einsum("ecd,edf->ecf", xe, mp["gate"], mesh_ctx)
        u = _ep_einsum("ecd,edf->ecf", xe, mp["up"], mesh_ctx)
    with jax.named_scope("lora_hook"):
        d_up = _hook_delta(server, "up", l, xe.reshape(E * C, -1), pairs, C,
                           mesh_ctx)
        d_up = d_up.reshape(E, C, -1) * lora_scale
        dg, du = jnp.split(d_up, 2, axis=-1)
    with jax.named_scope("moe_experts"):
        act = (jax.nn.silu(g + dg) * (u + du)).astype(x.dtype)
        # hook 2: down
        y = _ep_einsum("ecf,efd->ecd", act, mp["down"], mesh_ctx)
    with jax.named_scope("lora_hook"):
        d_dn = _hook_delta(server, "down", l, act.reshape(E * C, -1), pairs,
                           C, mesh_ctx)
        y = y + d_dn.reshape(E, C, -1) * lora_scale

    # combine with router weights (same bookkeeping as the coupled path)
    with jax.named_scope("moe_router"):
        slot_expert = jnp.arange(E * C, dtype=jnp.int32) // C
        match = ids[tok_safe] == slot_expert[:, None]
        w_slot = jnp.where(slot_tok < T,
                           jnp.sum(jnp.where(match, wts[tok_safe], 0.0), -1),
                           0.0)
        out = jnp.zeros((T + 1, cfg.d_model), F32)
        out = out.at[slot_tok].add(y.reshape(E * C, -1) * w_slot[:, None])
        return x + out[:T].reshape(B, 1, cfg.d_model).astype(x.dtype)


def disagg_decode_step_slots(params, cfg: ModelConfig, k_cache, v_cache,
                             tokens, pos_vec, server: LoRAServer,
                             adapter_ids, lora_scale: float, *,
                             block_table=None, mesh_ctx=None):
    """Continuous-batching disaggregated decode (per-slot positions).

    The slot-engine twin of ``transformer.decode_step_slots``: identical
    client math (embed -> attn -> MoE base GEMMs), with the LoRA deltas
    computed by the remote ``server`` at the two MoE hook points instead of
    in-model. Its regions carry stable ``jax.named_scope`` names —
    ``attention``, ``moe_router``, ``moe_experts``, ``lora_hook`` and
    ``lm_head`` — which a compiled program keeps in each instruction's
    metadata. tokens: (B, 1); pos_vec: (B,) int32 (-1 = inactive slot,
    its adapter id must be -1 too so the server contributes zero delta);
    k_cache/v_cache: (L, B, S, KV, hd) — or paged pools
    (L, n_pages, page_size, KV, hd) when ``block_table`` (B, nb) is given,
    mirroring the coupled slot step. ``mesh_ctx`` (a
    ``distributed.steps.ExpertParallelCtx``) runs the base expert GEMMs
    expert-parallel over its mesh — bit-identical by construction.

    Returns (logits (B, V), k_cache', v_cache').
    """
    assert cfg.is_moe, "disaggregated hooks target MoE FFNs (paper Fig. 3b)"
    x = ll.embed(tokens, params["embed"])
    positions = jnp.maximum(pos_vec, 0)[:, None]
    adapter_ids = jnp.asarray(adapter_ids)

    for l in range(cfg.n_layers):
        lp = _layer_params(params, l)
        with jax.named_scope("attention"):
            h = ll.rms_norm(x, lp["ln1"], cfg.norm_eps)
            q, k, v = ll.qkv_project(h, lp["attn"], cfg)
            q = ll.apply_rope(q, positions, cfg.rope_theta)
            k = ll.apply_rope(k, positions, cfg.rope_theta)
            if block_table is None:
                att, k_l, v_l = ll.decode_attention_update_slots(
                    q[:, 0], k[:, 0], v[:, 0], k_cache[l], v_cache[l],
                    pos_vec, window=cfg.sliding_window)
            else:
                att, k_l, v_l = _paged_attention(
                    q[:, 0], k[:, 0], v[:, 0], k_cache[l], v_cache[l],
                    block_table, pos_vec, cfg.sliding_window, mesh_ctx)
            k_cache = k_cache.at[l].set(k_l)
            v_cache = v_cache.at[l].set(v_l)
            x = x + ll.out_project(att[:, None], lp["attn"])
        x = _moe_hooks_layer(x, lp, cfg, l, server, adapter_ids, lora_scale,
                             mesh_ctx=mesh_ctx)

    with jax.named_scope("lm_head"):
        x = ll.rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = ll.unembed(x, params.get("lm_head", params["embed"]))
    return logits[:, 0], k_cache, v_cache


def disagg_decode_step(params, cfg: ModelConfig, cache: Dict, tokens,
                       server: LoRAServer, adapter_ids, lora_scale: float):
    """One decode step of a MoE model with disaggregated LoRA.

    tokens: (B, 1); adapter_ids: (B,) GLOBAL adapter ids (server resolves
    slots; non-resident ids must have been inserted by the cache manager).
    Returns (logits (B, V), new cache).
    """
    assert cfg.is_moe, "disaggregated hooks target MoE FFNs (paper Fig. 3b)"
    pos = cache["pos"]
    B = tokens.shape[0]
    x = ll.embed(tokens, params["embed"])
    positions = jnp.broadcast_to(pos[None, None], (B, 1)).astype(jnp.int32)
    new_k, new_v = cache["k"], cache["v"]

    for l in range(cfg.n_layers):
        lp = _layer_params(params, l)
        x, k_l, v_l = _client_attn(x, lp, cfg, pos, new_k[l], new_v[l],
                                   positions)
        new_k = new_k.at[l].set(k_l)
        new_v = new_v.at[l].set(v_l)
        x = _moe_hooks_layer(x, lp, cfg, l, server, adapter_ids, lora_scale)

    new_cache = dict(cache)
    new_cache["k"], new_cache["v"] = new_k, new_v
    new_cache["pos"] = pos + 1
    x = ll.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = ll.unembed(x, params.get("lm_head", params["embed"]))
    return logits[:, 0], new_cache
