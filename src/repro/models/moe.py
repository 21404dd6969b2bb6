"""Token-choice top-k MoE with static-capacity sort-based dispatch.

Two execution paths:
  - local (no mesh / smoke tests): every expert computed on-device
  - shard_map expert-parallel: tokens all_to_all'd along the expert-sharding
    axis, expert GEMMs run on the owning shard; supports an ``ff_axis`` that
    shards the expert hidden dim at compute time (psum after down-proj) and an
    ``fsdp_axis`` whose at-rest weight shards are all-gathered per layer.

The plan (which mesh axis plays which role) is resolved from the active
ShardingRules at trace time — see ``resolve_moe_plan``:
  train:  experts -> "model" (seq-sharded tokens a2a along model),
          ff at rest -> "data" (FSDP, gathered per layer)
  decode: experts -> "data" (batch-sharded tokens a2a along data),
          ff -> "model" at compute (psum; tokens replicated across model)
Non-divisible expert counts degrade gracefully (experts replicated,
ff compute-sharded) — the correctness invariant is that ``ep_axis`` must
shard tokens, and ``ff_axis`` must NOT shard tokens.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map

from repro.distributed.sharding import active_rules, mesh_axis_size

F32 = jnp.float32


# ------------------------------ routing --------------------------------- #
def route(x_flat, router_w, n_experts: int, top_k: int):
    """x_flat: (T, d) -> (ids (T,K) int32, weights (T,K) f32)."""
    logits = jnp.einsum("td,de->te", x_flat, router_w,
                        preferred_element_type=F32)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, ids = jax.lax.top_k(probs, top_k)
    weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)
    return ids.astype(jnp.int32), weights


def capacity(n_tokens: int, top_k: int, n_experts: int, cf: float,
             dropless: bool = False) -> int:
    """Static per-expert slot count. ``dropless`` (decode): worst case, every
    pair lands on one expert — exact but only affordable for small T."""
    if dropless:
        c = n_tokens * top_k
    else:
        c = int(cf * n_tokens * top_k / n_experts) + 1
    return max(4, -(-c // 4) * 4)  # round up to a multiple of 4, floor 4


# ----------------------- local dispatch/combine ------------------------- #
def local_dispatch(x_flat, ids, C: int, n_experts: int):
    """Group tokens by expert into an (E, C, d) buffer (overflow dropped).

    Returns (xe (E,C,d), slot_tok (E*C,) token index per slot with T==OOB).
    """
    T, d = x_flat.shape
    K = ids.shape[1]
    flat_ids = ids.reshape(-1)  # (T*K,)
    sort_idx = jnp.argsort(flat_ids, stable=True)
    sorted_ids = flat_ids[sort_idx]
    counts = jnp.bincount(flat_ids, length=n_experts)
    starts = jnp.cumsum(counts) - counts
    pos_in_e = jnp.arange(T * K) - starts[sorted_ids]
    keep = pos_in_e < C
    slot = sorted_ids * C + jnp.where(keep, pos_in_e, 0)
    tok_idx = (sort_idx // K).astype(jnp.int32)
    slot_tok = jnp.full((n_experts * C,), T, dtype=jnp.int32)
    slot_tok = slot_tok.at[jnp.where(keep, slot, n_experts * C)].set(
        tok_idx, mode="drop")
    x_pad = jnp.concatenate([x_flat, jnp.zeros((1, d), x_flat.dtype)], axis=0)
    xe = x_pad[slot_tok].reshape(n_experts, C, d)
    return xe, slot_tok


def expert_ffn(xe, wg, wu, wd, gated: bool = True, lora=None,
               row_adapter=None, expert_offset=0, lora_scale=1.0):
    """xe: (E, C, d); wg/wu: (E, d, f); wd: (E, f, d) -> (E, C, d).

    When ``lora`` holds expert-specific adapter stacks (A: (N, E_total, d, r),
    B: (N, E_total, r, f)), each row's delta x @ A[a, e] @ B[a, e] is added —
    the paper's two MoE hook points (up/gate and down). ``row_adapter``:
    (E*C,) adapter id per dispatch slot, -1 = inactive. ``expert_offset``:
    global id of local expert 0 (expert-parallel shards).
    """
    E, C, d = xe.shape

    def dl(name, rows_in):
        if lora is None or name not in lora:
            return None
        from repro.kernels import ops
        with jax.named_scope("lora_hook"):
            row_e = expert_offset + jnp.arange(E * C, dtype=jnp.int32) // C
            return ops.bgmv_expert(
                rows_in.reshape(E * C, -1), lora[name]["A"], lora[name]["B"],
                row_adapter, row_e).reshape(E, C, -1) * lora_scale

    if gated:
        g = jnp.einsum("ecd,edf->ecf", xe, wg, preferred_element_type=F32)
        u = jnp.einsum("ecd,edf->ecf", xe, wu, preferred_element_type=F32)
        dg, du = dl("gate", xe), dl("up", xe)
        if dg is not None:
            g = g + dg
        if du is not None:
            u = u + du
        h = (jax.nn.silu(g) * u).astype(xe.dtype)
    else:
        u = jnp.einsum("ecd,edf->ecf", xe, wu, preferred_element_type=F32)
        du = dl("up", xe)
        if du is not None:
            u = u + du
        h = jax.nn.gelu(u).astype(xe.dtype)
    y = jnp.einsum("ecf,efd->ecd", h, wd, preferred_element_type=F32)
    dd = dl("down", h)
    if dd is not None:
        y = y + dd
    return y


# ------------------------------- plans ---------------------------------- #
@dataclasses.dataclass(frozen=True)
class MoEPlan:
    ep_axis: Optional[str]      # axis sharding experts (must shard tokens)
    ff_axis: Optional[str]      # axis sharding ff at compute (psum after)
    fsdp_axis: Optional[str]    # axis sharding ff at rest (gathered per layer)
    token_batch_axes: tuple     # mesh axes sharding the token batch dim
    token_seq_axis: Optional[str]


def resolve_moe_plan(cfg, batch: int, n_tokens_seq: int,
                     kind: str) -> Optional[MoEPlan]:
    """Derive the MoE execution plan from the active sharding rules.

    Invariants enforced here:
      - ``ep_axis`` (expert sharding, a2a exchange) must be an axis that
        shards tokens, else dispatch would duplicate work.
      - ``ff_axis`` (compute-time ff sharding, psum after down-proj) must NOT
        shard tokens, else the psum would mix different tokens' partials.
      - an at-rest ff shard axis that *does* shard tokens becomes
        ``fsdp_axis``: gathered per layer before compute (ZeRO-3 style).
    """
    rules = active_rules()
    if rules is None:
        return None

    def ax(name, size=None):
        r = rules._resolve(name, size)
        if r is None:
            return None
        return r if isinstance(r, str) else r[0]

    batch_axes = rules.spec(["batch"], [batch])[0]
    if batch_axes is None:
        batch_axes = ()
    elif isinstance(batch_axes, str):
        batch_axes = (batch_axes,)
    batch_axes = tuple(batch_axes)
    seq_axis = ax("seq", n_tokens_seq) if kind != "decode" else None
    token_axes = set(batch_axes) | ({seq_axis} if seq_axis else set())

    ep = ax("experts", cfg.n_experts)
    if ep is not None and ep not in token_axes:
        ep = None
    ff_rest = ax("moe_ff", cfg.d_ff)

    if ep is not None:
        if ff_rest is None:
            return MoEPlan(ep, None, None, batch_axes, seq_axis)
        if ff_rest in token_axes:
            return MoEPlan(ep, None, ff_rest, batch_axes, seq_axis)
        return MoEPlan(ep, ff_rest, None, batch_axes, seq_axis)

    # experts not shardable: replicate them; shard ff at compute on a
    # non-token axis, gathering the sequence across it if needed.
    ff_axis = ff_rest if (ff_rest and ff_rest not in batch_axes) else None
    if ff_axis is None:
        cand = ax("mlp", cfg.d_ff)
        ff_axis = cand if (cand and cand not in batch_axes) else None
    token_seq = None if (seq_axis is not None and seq_axis == ff_axis) else seq_axis
    return MoEPlan(None, ff_axis, None, batch_axes, token_seq)


# ------------------------------ the block ------------------------------- #
def moe_block(x, params, cfg, kind: str = "train", lora=None, ids_tok=None,
              lora_scale: float = 1.0):
    """x: (B, S, d) -> (B, S, d). params: router (d,E), gate/up/down (E,d,f).

    ``lora``: optional expert-LoRA stacks {gate/up/down: {A, B}} (coupled
    S-LoRA path); ``ids_tok``: (T,) adapter id per token.
    """
    B, S, d = x.shape
    plan = resolve_moe_plan(cfg, B, S, kind)
    moe_lora = None
    if lora is not None and any(n in lora for n in ("gate", "up", "down")):
        moe_lora = {n: lora[n] for n in ("gate", "up", "down") if n in lora}
    if plan is None:
        return _moe_local(x, params, cfg, moe_lora, ids_tok, lora_scale)
    return _moe_sharded(x, params, cfg, plan, kind, moe_lora, ids_tok,
                        lora_scale)


def _moe_local(x, params, cfg, lora=None, ids_tok=None, lora_scale=1.0):
    B, S, d = x.shape
    xf = x.reshape(-1, d)
    T = xf.shape[0]
    with jax.named_scope("moe_router"):
        ids, wts = route(xf, params["router"], cfg.n_experts, cfg.top_k)
    C = capacity(T, cfg.top_k, cfg.n_experts, cfg.capacity_factor,
                 dropless=(T * cfg.top_k <= 4096))
    y = _dispatch_compute_combine(xf, ids, wts, params["gate"], params["up"],
                                  params["down"], cfg, C, lora=lora,
                                  token_ads=ids_tok, lora_scale=lora_scale)
    return y.reshape(B, S, d).astype(x.dtype)


def _dispatch_compute_combine(xf, ids, wts, wg, wu, wd, cfg, C,
                              ep_axis=None, ff_axis=None, lora=None,
                              token_ads=None, lora_scale=1.0):
    """Shared core: dispatch -> (exchange) -> expert ffn -> (exchange) -> combine.

    Runs either outside shard_map (ep_axis/ff_axis None) or inside (manual
    collectives). Token/expert bookkeeping is identical in both cases.
    """
    T, d = xf.shape
    E = cfg.n_experts
    with jax.named_scope("moe_router"):
        xe, slot_tok = local_dispatch(xf, ids, C, E)  # (E, C, d)

    row_adapter = None
    if lora is not None and token_ads is not None:
        tok_safe = jnp.minimum(slot_tok, T - 1)
        row_adapter = jnp.where(slot_tok < T, token_ads[tok_safe], -1)

    expert_offset = 0
    if ep_axis is not None:
        ep = mesh_axis_size(ep_axis)
        E_loc = E // ep
        # tiled a2a: (E, C, d) -> (E_loc, ep*C, d); each ep rank keeps its
        # expert block and receives those experts' rows from all peers
        xe = jax.lax.all_to_all(xe, ep_axis, split_axis=0, concat_axis=1,
                                tiled=True)
        if row_adapter is not None:
            ra = jax.lax.all_to_all(row_adapter.reshape(E, C), ep_axis,
                                    split_axis=0, concat_axis=1, tiled=True)
            row_adapter = ra.reshape(-1)
        expert_offset = jax.lax.axis_index(ep_axis) * E_loc

    with jax.named_scope("moe_experts"):
        y_e = expert_ffn(xe, wg, wu, wd, cfg.gated_mlp, lora=lora,
                         row_adapter=row_adapter,
                         expert_offset=expert_offset, lora_scale=lora_scale)
    if ff_axis is not None:
        y_e = jax.lax.psum(y_e, ff_axis)

    if ep_axis is not None:
        # reverse tiled a2a: (E_loc, ep*C, d) -> (E, C, d)
        y_e = jax.lax.all_to_all(y_e, ep_axis, split_axis=1, concat_axis=0,
                                 tiled=True)

    # combine with router weights: weight per slot via gather from (T,K)
    with jax.named_scope("moe_router"):
        y_slots = y_e.reshape(-1, d)
        out = jnp.zeros((T + 1, d), F32)
        # recover per-slot weights: slot_tok gives token; match expert of
        # slot
        slot_expert = jnp.arange(slot_tok.shape[0]) // C
        tok_safe = jnp.minimum(slot_tok, T - 1)
        match = ids[tok_safe] == slot_expert[:, None]  # (E*C, K)
        w_slot = jnp.where(
            slot_tok < T,
            jnp.sum(jnp.where(match, wts[tok_safe], 0.0), axis=-1), 0.0)
        out = out.at[slot_tok].add(y_slots.astype(F32) * w_slot[:, None])
        return out[:T]


def _moe_sharded(x, params, cfg, plan: MoEPlan, kind: str, lora=None,
                 ids_tok=None, lora_scale=1.0):
    rules = active_rules()
    B, S, d = x.shape
    E, ff = cfg.n_experts, cfg.d_ff
    mesh = rules.mesh

    batch_spec = plan.token_batch_axes or None
    x_spec = P(batch_spec, plan.token_seq_axis, None)
    router_spec = P(None, None)

    ep, ffa, fsdp = plan.ep_axis, plan.ff_axis, plan.fsdp_axis
    E_sh = ep if ep else None
    # weights at rest: gate/up (E, d, ff), down (E, ff, d)
    gu_spec = P(E_sh, None, ffa if ffa else fsdp)
    dn_spec = P(E_sh, ffa if ffa else fsdp, None)

    gated = cfg.gated_mlp
    operands = [x, params["router"], params["up"], params["down"]]
    specs = [x_spec, router_spec, gu_spec, dn_spec]
    if gated:
        operands.append(params["gate"])
        specs.append(gu_spec)
    has_ids = ids_tok is not None
    if has_ids:
        operands.append(ids_tok.reshape(B, S))
        specs.append(P(batch_spec, plan.token_seq_axis))
    lora_names = sorted(lora) if lora else []
    for n in lora_names:  # adapter pools replicated (the coupled baseline)
        operands += [lora[n]["A"], lora[n]["B"]]
        specs += [P(*([None] * lora[n]["A"].ndim)),
                  P(*([None] * lora[n]["B"].ndim))]

    # decode with expert parallelism: capacity-padded a2a buffers are ~99%
    # empty at decode token counts (measured 0.76 s collective per step on
    # qwen3-moe) — instead all-gather the few tokens, mask to local experts,
    # and psum the combined output (EXPERIMENTS.md §Perf opt-C).
    use_allgather = kind == "decode" and ep is not None

    def body(*args):
        it = iter(args)
        x_l, rw, wu, wd = next(it), next(it), next(it), next(it)
        wg = next(it) if gated else wu
        ids_l = next(it) if has_ids else None
        lora_l = {n: {"A": next(it), "B": next(it)} for n in lora_names} or None
        Bl, Sl, _ = x_l.shape
        xf = x_l.reshape(-1, d)
        if fsdp and not ffa:  # FSDP: gather ff shards for this layer
            wu = jax.lax.all_gather(wu, fsdp, axis=2, tiled=True)
            wg = jax.lax.all_gather(wg, fsdp, axis=2, tiled=True) if gated else wu
            wd = jax.lax.all_gather(wd, fsdp, axis=1, tiled=True)
        token_ads = None if ids_l is None else ids_l.reshape(-1)

        if use_allgather:
            y = _decode_allgather_moe(xf, rw, wg, wu, wd, cfg, ep, ffa,
                                      lora_l, token_ads, lora_scale)
            return y.reshape(Bl, Sl, d)

        T = xf.shape[0]
        ids, wts = route(xf, rw, E, cfg.top_k)
        C = capacity(T, cfg.top_k, E, cfg.capacity_factor,
                     dropless=(kind == "decode"))
        y = _dispatch_compute_combine(
            xf, ids, wts, wg, wu, wd, cfg, C, ep_axis=ep, ff_axis=ffa,
            lora=lora_l, token_ads=token_ads, lora_scale=lora_scale)
        return y.reshape(Bl, Sl, d)

    fn = shard_map(body, mesh=mesh, in_specs=tuple(specs), out_specs=x_spec,
                   check_vma=False)
    y = fn(*operands)
    return y.astype(x.dtype)


def _decode_allgather_moe(xf, rw, wg, wu, wd, cfg, ep_axis, ff_axis,
                          lora, token_ads, lora_scale):
    """Decode MoE: gather the (few) tokens across the expert axis, compute
    each shard's LOCAL experts for all tokens (dropless: per-expert slots =
    T since a token routes to an expert at most once), psum the combined
    result over (ep, ff) and slice back the caller's tokens. Exactly
    equivalent to dropless a2a dispatch, at ~1% of its collective bytes."""
    E, K = cfg.n_experts, cfg.top_k
    d = xf.shape[-1]
    T_loc = xf.shape[0]
    ep = mesh_axis_size(ep_axis)
    E_loc = E // ep
    rank = jax.lax.axis_index(ep_axis)

    xg = jax.lax.all_gather(xf, ep_axis, axis=0, tiled=True)   # (T, d)
    T = xg.shape[0]
    ads = None
    if token_ads is not None:
        ads = jax.lax.all_gather(token_ads, ep_axis, axis=0, tiled=True)
    ids, wts = route(xg, rw, E, K)                             # (T, K)
    e0 = rank * E_loc
    local = (ids >= e0) & (ids < e0 + E_loc)
    ids_masked = jnp.where(local, ids - e0, E_loc)  # E_loc = dummy bucket
    C = max(4, -(-T // 4) * 4)  # a token hits an expert at most once
    xe, slot_tok = local_dispatch(xg, ids_masked, C, E_loc + 1)
    xe = xe[:E_loc]
    row_adapter = None
    if lora is not None and ads is not None:
        tok_safe = jnp.minimum(slot_tok, T - 1)
        ra = jnp.where(slot_tok < T, ads[tok_safe], -1)
        row_adapter = ra.reshape(E_loc + 1, C)[:E_loc].reshape(-1)
    y_e = expert_ffn(xe, wg, wu, wd, cfg.gated_mlp, lora=lora,
                     row_adapter=row_adapter, expert_offset=e0,
                     lora_scale=lora_scale)
    # combine LOCAL contributions into the full token set
    slot_tok_loc = slot_tok.reshape(E_loc + 1, C)[:E_loc].reshape(-1)
    slot_expert = jnp.arange(E_loc * C, dtype=jnp.int32) // C
    tok_safe = jnp.minimum(slot_tok_loc, T - 1)
    match = ids_masked[tok_safe] == slot_expert[:, None]
    w_slot = jnp.where(slot_tok_loc < T,
                       jnp.sum(jnp.where(match, wts[tok_safe], 0.0), -1), 0.0)
    out = jnp.zeros((T + 1, d), F32)
    out = out.at[slot_tok_loc].add(y_e.reshape(-1, d) * w_slot[:, None])
    out = out[:T]
    axes = (ep_axis,) + ((ff_axis,) if ff_axis else ())
    out = jax.lax.psum(out, axes)
    return jax.lax.dynamic_slice_in_dim(out, rank * T_loc, T_loc, axis=0)
