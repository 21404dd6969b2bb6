"""Model forward passes: train/prefill (parallel) and decode (incremental).

One ``forward`` covers dense / moe / vlm decoder LMs, rwkv6, zamba2 hybrid,
and the audio encoder-decoder; ``decode_step`` is the serving-side single
token step. Layers run under lax.scan over stacked params (compile-time O(1)
in depth); train wraps the layer body in jax.checkpoint.

Coupled multi-LoRA (S-LoRA-style batched adapters) threads through
``lora_ctx``; the disaggregated client path instead passes ``lora_ctx=None``
and exports hook activations (see repro.core.disagg).
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.distributed.sharding import constrain
from repro.models import layers as ll
from repro.models import moe as moe_mod
from repro.models import ssm

F32 = jnp.float32


# --------------------------------------------------------------------- #
# LoRA helpers (coupled path)                                             #
# --------------------------------------------------------------------- #
def _lora_slice(lora_ctx, names):
    """Pull per-layer adapter stacks for scan xs; None if absent."""
    if lora_ctx is None:
        return None
    out = {}
    for n in names:
        if n in lora_ctx["adapters"]:
            out[n] = lora_ctx["adapters"][n]
    return out or None


def _delta(xf, lora_layer, name, ids_tok, scale):
    if lora_layer is None or name not in lora_layer:
        return None
    from repro.kernels import ops
    ab = lora_layer[name]
    return ops.bgmv(xf, ab["A"], ab["B"], ids_tok) * scale


# --------------------------------------------------------------------- #
# Attention block (shared by all attention-bearing families)             #
# --------------------------------------------------------------------- #
def attn_block(x, ap, cfg, positions, *, causal=True, window=0,
               kv_override=None, rope=True, lora_layer=None, ids_tok=None,
               lora_scale=1.0):
    """x: (B, S, d). Returns (y, (k, v)) — k/v post-RoPE for caching.

    kv_override: (k, v) tensors to attend over instead of self-derived
    (cross-attention); then only q is computed from x.
    """
    B, S, d = x.shape
    if S > 1:
        # single sequence-parallel gather point: gather the residual ONCE
        # here instead of per projection (§Perf opt-B: per-projection
        # gathers tripled the all-gather volume on 72B train)
        x = constrain(x, "batch", None, "embed")
    q, k, v = ll.qkv_project(x, ap, cfg)
    if lora_layer is not None:
        xf = x.reshape(-1, d)
        for name, tgt, shape in (("q", q, (B, S, cfg.n_heads, cfg.head_dim)),
                                 ("k", k, (B, S, cfg.n_kv_heads, cfg.head_dim)),
                                 ("v", v, (B, S, cfg.n_kv_heads, cfg.head_dim))):
            dlt = _delta(xf, lora_layer, name, ids_tok, lora_scale)
            if dlt is not None:
                if name == "q":
                    q = q + dlt.reshape(shape).astype(q.dtype)
                elif name == "k":
                    k = k + dlt.reshape(shape).astype(k.dtype)
                else:
                    v = v + dlt.reshape(shape).astype(v.dtype)
    if rope:
        q = ll.apply_rope(q, positions, cfg.rope_theta)
        k = ll.apply_rope(k, positions, cfg.rope_theta)
    if kv_override is not None:
        k, v = kv_override
        attn = ll.causal_attention(q, k, v, causal=False, window=0)
    else:
        attn = ll.causal_attention(q, k, v, causal=causal, window=window)
    y = ll.out_project(attn, ap)
    if lora_layer is not None:
        dlt = _delta(attn.reshape(B * S, -1), lora_layer, "o", ids_tok,
                     lora_scale)
        if dlt is not None:
            y = y + dlt.reshape(B, S, d).astype(y.dtype)
    return y, (k, v)


def _mlp_with_lora(h, mp, cfg, lora_layer, ids_tok, lora_scale):
    """Exact multi-LoRA MLP: adapters perturb gate/up/down weights."""
    has = lora_layer is not None and any(n in lora_layer
                                         for n in ("gate", "up", "down"))
    if not has:
        return ll.mlp(h, mp, cfg)
    B, S, d = h.shape
    xf = h.reshape(-1, d)

    def with_delta(base, name):
        dlt = _delta(xf, lora_layer, name, ids_tok, lora_scale)
        return base if dlt is None else base + dlt.reshape(base.shape)

    if cfg.gated_mlp:
        g = with_delta(jnp.einsum("bsd,df->bsf", h, mp["gate"],
                                  preferred_element_type=F32), "gate")
        u = with_delta(jnp.einsum("bsd,df->bsf", h, mp["up"],
                                  preferred_element_type=F32), "up")
        act = (jax.nn.silu(g) * u).astype(h.dtype)
    else:
        u = with_delta(jnp.einsum("bsd,df->bsf", h, mp["up"],
                                  preferred_element_type=F32), "up")
        act = jax.nn.gelu(u).astype(h.dtype)
    y = jnp.einsum("bsf,fd->bsd", act, mp["down"], preferred_element_type=F32)
    dlt = _delta(act.reshape(B * S, -1), lora_layer, "down", ids_tok,
                 lora_scale)
    if dlt is not None:
        y = y + dlt.reshape(y.shape)
    return y.astype(h.dtype)


# --------------------------------------------------------------------- #
# Decoder-only LM (dense / moe / vlm)                                    #
# --------------------------------------------------------------------- #
def _decoder_layer(x, lp, lora_layer, cfg, positions, kind, ids_tok,
                   lora_scale, collect_kv):
    h = ll.rms_norm(x, lp["ln1"], cfg.norm_eps)
    att, kv = attn_block(h, lp["attn"], cfg, positions,
                         window=cfg.sliding_window,
                         lora_layer=lora_layer, ids_tok=ids_tok,
                         lora_scale=lora_scale)
    x = constrain(x + att, "batch", "seq", "embed")
    h = ll.rms_norm(x, lp["ln2"], cfg.norm_eps)
    if cfg.is_moe:
        y = moe_mod.moe_block(h, lp["moe"], cfg, kind=kind,
                              lora=lora_layer, ids_tok=ids_tok,
                              lora_scale=lora_scale)
    else:
        y = _mlp_with_lora(h, lp["mlp"], cfg, lora_layer, ids_tok, lora_scale)
    x = constrain(x + y, "batch", "seq", "embed")
    return x, (kv if collect_kv else None)


def _embed_inputs(params, cfg, tokens, frontend_emb):
    x = ll.embed(tokens, params["embed"])
    if cfg.frontend and frontend_emb is not None:
        x = jnp.concatenate([frontend_emb.astype(x.dtype), x], axis=1)
    return constrain(x, "batch", "seq", "embed")


def forward(params, cfg, tokens, frontend_emb=None, kind="train",
            lora_ctx=None, collect_kv=False, unembed=True):
    """Parallel forward. tokens: (B, S_text); frontend_emb: (B, S_front, d).

    Returns (logits (B, S, V), aux) where aux holds per-layer K/V stacks when
    collect_kv (prefill) or SSM final states for recurrent families.
    ``unembed=False`` (KV-only prefill, attention LMs) skips the final norm
    and lm-head GEMM and returns (None, aux).
    """
    fam = cfg.family
    if fam == "audio":
        return _forward_encdec(params, cfg, tokens, frontend_emb, kind,
                               collect_kv)
    if fam == "ssm" and cfg.rwkv:
        return _forward_rwkv(params, cfg, tokens, kind)
    if fam == "hybrid":
        return _forward_hybrid(params, cfg, tokens, kind, collect_kv)

    x = _embed_inputs(params, cfg, tokens, frontend_emb)
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    ids_tok = None
    lora_scale = 1.0
    if lora_ctx is not None:
        ids_tok = jnp.repeat(lora_ctx["ids"], S)
        lora_scale = lora_ctx["scale"]
    lora_stack = _lora_slice(lora_ctx, ("q", "k", "v", "o", "gate", "up",
                                        "down"))

    def body(x, xs):
        lp, lora_layer = xs
        return _decoder_layer(x, lp, lora_layer, cfg, positions, kind,
                              ids_tok, lora_scale, collect_kv)

    if kind == "train":
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.nothing_saveable)
    x, kvs = jax.lax.scan(body, x, (params["layers"], lora_stack))
    if not unembed:
        return None, kvs
    x = ll.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = ll.unembed(x, params.get("lm_head", params["embed"]))
    return logits, kvs


# --------------------------------------------------------------------- #
# RWKV-6                                                                  #
# --------------------------------------------------------------------- #
def _forward_rwkv(params, cfg, tokens, kind):
    x = ll.embed(tokens, params["embed"])
    x = constrain(x, "batch", None, "embed")
    B, S, d = x.shape
    state0 = ssm.rwkv6_init_state(cfg, B)

    def body(x, lp):
        h = ll.rms_norm(x, lp["ln1"], cfg.norm_eps)
        y, st = ssm.rwkv6_time_mix(h, lp, cfg, state0)
        x = x + y
        h = ll.rms_norm(x, lp["ln2"], cfg.norm_eps)
        y, st2 = ssm.rwkv6_channel_mix(h, lp, cfg, st)
        x = x + y
        return constrain(x, "batch", None, "embed"), \
            (st2.shift_tm, st2.shift_cm, st2.wkv)

    if kind == "train":
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.nothing_saveable)
    x, states = jax.lax.scan(body, x, params["layers"])
    x = ll.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = ll.unembed(x, params.get("lm_head", params["embed"]))
    return logits, states


# --------------------------------------------------------------------- #
# Zamba2 hybrid (mamba2 backbone + weight-shared attention blocks)        #
# --------------------------------------------------------------------- #
def _shared_block(x, sp, cfg, positions, window):
    h = ll.rms_norm(x, sp["ln1"], cfg.norm_eps)
    att, kv = attn_block(h, sp["attn"], cfg, positions, window=window)
    x = x + att
    h = ll.rms_norm(x, sp["ln2"], cfg.norm_eps)
    x = x + ll.mlp(h, sp["mlp"], cfg)
    return x, kv


def _forward_hybrid(params, cfg, tokens, kind, collect_kv):
    x = ll.embed(tokens, params["embed"])
    x = constrain(x, "batch", None, "embed")
    B, S, d = x.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    window = cfg.sliding_window
    sp = params["shared_attn"]

    def mamba_body(x, lp):
        y, st = ssm.mamba2_forward(x, lp, cfg, None)
        return x + y, (st.h, st.conv)

    if kind == "train":
        mamba_body = jax.checkpoint(
            mamba_body, policy=jax.checkpoint_policies.nothing_saveable)

    def group(x, glp):
        x, kv = _shared_block(x, sp, cfg, positions, window)
        x, states = jax.lax.scan(mamba_body, x, glp)
        return x, (kv if collect_kv else None, states if collect_kv else None)

    x, aux = jax.lax.scan(group, x, params["layers"])
    x = ll.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = ll.unembed(x, params.get("lm_head", params["embed"]))
    return logits, aux


# --------------------------------------------------------------------- #
# Audio encoder-decoder (frontend embeddings -> encoder -> decoder)      #
# --------------------------------------------------------------------- #
def _forward_encdec(params, cfg, tokens, frontend_emb, kind, collect_kv):
    # encoder: bidirectional over frontend frames
    enc = constrain(frontend_emb.astype(jnp.bfloat16), "batch", "seq", "embed")
    B, Se, d = enc.shape
    enc_pos = jnp.broadcast_to(jnp.arange(Se, dtype=jnp.int32), (B, Se))

    def enc_body(x, lp):
        h = ll.rms_norm(x, lp["ln1"], cfg.norm_eps)
        att, _ = attn_block(h, lp["attn"], cfg, enc_pos, causal=False)
        x = x + att
        h = ll.rms_norm(x, lp["ln2"], cfg.norm_eps)
        x = constrain(x + ll.mlp(h, lp["mlp"], cfg), "batch", "seq", "embed")
        return x, None

    if kind == "train":
        enc_body = jax.checkpoint(
            enc_body, policy=jax.checkpoint_policies.nothing_saveable)
    enc, _ = jax.lax.scan(enc_body, enc, params["enc_layers"])
    enc = ll.rms_norm(enc, params["enc_norm"], cfg.norm_eps)

    # decoder
    x = ll.embed(tokens, params["embed"])
    x = constrain(x, "batch", "seq", "embed")
    B, Sd, _ = x.shape
    dec_pos = jnp.broadcast_to(jnp.arange(Sd, dtype=jnp.int32), (B, Sd))

    def dec_body(x, lp):
        h = ll.rms_norm(x, lp["ln1"], cfg.norm_eps)
        att, kv = attn_block(h, lp["attn"], cfg, dec_pos)
        x = x + att
        h = ll.rms_norm(x, lp["ln2"], cfg.norm_eps)
        # cross-attention: k/v from encoder output via this layer's weights
        _, ck, cv = ll.qkv_project(enc, lp["cross"], cfg)
        catt, _ = attn_block(h, lp["cross"], cfg, dec_pos, rope=False,
                             kv_override=(ck, cv))
        x = x + catt
        h = ll.rms_norm(x, lp["ln3"], cfg.norm_eps)
        x = constrain(x + ll.mlp(h, lp["mlp"], cfg), "batch", "seq", "embed")
        return x, ((kv, (ck, cv)) if collect_kv else None)

    if kind == "train":
        dec_body = jax.checkpoint(
            dec_body, policy=jax.checkpoint_policies.nothing_saveable)
    x, kvs = jax.lax.scan(dec_body, x, params["layers"])
    x = ll.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = ll.unembed(x, params.get("lm_head", params["embed"]))
    return logits, kvs


# --------------------------------------------------------------------- #
# Chunked prefill (parallel within a chunk, incremental across chunks)    #
# --------------------------------------------------------------------- #
def prefill_chunk(params, cfg, tokens, k_ctx, v_ctx):
    """One fixed-size prefill chunk attending over previously-cached KV.

    Chunked prefill admits a long prompt as a series of small parallel
    forwards instead of one power-of-two-padded shot: chunk c computes
    self-attention for its C tokens against [all earlier chunks' KV | this
    chunk], so the math is position-for-position identical to a monolithic
    ``forward(collect_kv=True)`` while the peak activation is O(C) and the
    KV for earlier chunks can already live in cache rows or pages.

    tokens: (B, C); k_ctx/v_ctx: (L, B, S_ctx, KV, hd) the earlier chunks'
    KV (S_ctx may be 0; it sets the position offset, so it must hold
    exactly the first S_ctx positions). LoRA-free, like all prefill here
    (paper footnote 1: prefill runs on separate LoRA-free instances under
    PD disaggregation). dense/moe/vlm only. No lm-head (admission needs
    only the KV). Named scopes as in the disaggregated decode step:
    ``attention`` here, ``moe_router``/``moe_experts`` in the MoE block.

    Returns (k_chunk, v_chunk), each (L, B, C, KV, hd).
    """
    fam = cfg.family
    if fam not in ("dense", "moe", "vlm"):
        raise ValueError(f"chunked prefill supports attention LMs, not {fam}")
    x = _embed_inputs(params, cfg, tokens, None)
    B, C, _ = x.shape
    pos0 = k_ctx.shape[2]
    positions = jnp.broadcast_to(pos0 + jnp.arange(C, dtype=jnp.int32),
                                 (B, C))

    def body(x, xs):
        lp, kc, vc = xs
        with jax.named_scope("attention"):
            h = ll.rms_norm(x, lp["ln1"], cfg.norm_eps)
            q, k, v = ll.qkv_project(h, lp["attn"], cfg)
            q = ll.apply_rope(q, positions, cfg.rope_theta)
            k = ll.apply_rope(k, positions, cfg.rope_theta)
            k_full = jnp.concatenate([kc.astype(k.dtype), k], axis=1)
            v_full = jnp.concatenate([vc.astype(v.dtype), v], axis=1)
            attn = ll.causal_attention(q, k_full, v_full, causal=True,
                                       window=cfg.sliding_window,
                                       q_offset=pos0)
            x = x + ll.out_project(attn, lp["attn"])
        h = ll.rms_norm(x, lp["ln2"], cfg.norm_eps)
        if cfg.is_moe:
            y = moe_mod.moe_block(h, lp["moe"], cfg, kind="decode")
        else:
            y = ll.mlp(h, lp["mlp"], cfg)
        x = x + y
        return x, (k, v)

    _, kvs = jax.lax.scan(body, x, (params["layers"], k_ctx, v_ctx))
    return kvs


# --------------------------------------------------------------------- #
# Continuous-batching decode step (per-slot positions)                    #
# --------------------------------------------------------------------- #
def decode_step_slots(params, cfg, k_cache, v_cache, tokens, pos_vec,
                      lora_ctx=None, *, block_table=None):
    """One decode token for a batch of engine SLOTS with per-slot positions.

    The continuous-batching data plane: rows are slots admitted/evicted at
    step boundaries, so each carries its own sequence length. tokens: (B, 1);
    pos_vec: (B,) int32 position of this token per slot (-1 = inactive slot:
    no cache write, garbage logits). k_cache/v_cache: (L, B, S, KV, hd) —
    or, when ``block_table`` (B, nb) is given, PAGED pools
    (L, n_pages, page_size, KV, hd) shared by all slots, with per-row page
    ids resolving each write/read (see layers
    .decode_attention_update_slots_paged). dense/moe/vlm families only (the
    serving targets); no int8 KV.

    Returns (logits (B, V), k_cache', v_cache').
    """
    fam = cfg.family
    if fam not in ("dense", "moe", "vlm"):
        raise ValueError(f"slot decode supports attention LMs, not {fam}")
    B = tokens.shape[0]
    x = ll.embed(tokens, params["embed"])
    positions = jnp.maximum(pos_vec, 0)[:, None]  # (B, 1) for RoPE

    ids_tok = lora_ctx["ids"] if lora_ctx is not None else None
    lora_scale = lora_ctx["scale"] if lora_ctx is not None else 1.0
    lora_stack = _lora_slice(lora_ctx, ("q", "k", "v", "o", "gate", "up",
                                        "down"))

    def body(carry, xs):
        x, k_all, v_all, l = carry
        lp, lora_layer = xs
        h = ll.rms_norm(x, lp["ln1"], cfg.norm_eps)
        q, k, v = ll.qkv_project(h, lp["attn"], cfg)
        if lora_layer is not None:
            xf = h.reshape(B, -1)
            for name in ("q", "k", "v"):
                dlt = _delta(xf, lora_layer, name, ids_tok, lora_scale)
                if dlt is not None:
                    if name == "q":
                        q = q + dlt.reshape(q.shape).astype(q.dtype)
                    elif name == "k":
                        k = k + dlt.reshape(k.shape).astype(k.dtype)
                    else:
                        v = v + dlt.reshape(v.shape).astype(v.dtype)
        q = ll.apply_rope(q, positions, cfg.rope_theta)
        k = ll.apply_rope(k, positions, cfg.rope_theta)
        k_l = jax.lax.dynamic_index_in_dim(k_all, l, 0, keepdims=False)
        v_l = jax.lax.dynamic_index_in_dim(v_all, l, 0, keepdims=False)
        if block_table is None:
            att, k_l, v_l = ll.decode_attention_update_slots(
                q[:, 0], k[:, 0], v[:, 0], k_l, v_l, pos_vec,
                window=cfg.sliding_window)
        else:
            att, k_l, v_l = ll.decode_attention_update_slots_paged(
                q[:, 0], k[:, 0], v[:, 0], k_l, v_l, block_table, pos_vec,
                window=cfg.sliding_window)
        k_all = jax.lax.dynamic_update_index_in_dim(k_all, k_l, l, 0)
        v_all = jax.lax.dynamic_update_index_in_dim(v_all, v_l, l, 0)
        att = att[:, None]  # (B, 1, H, hd)
        y = ll.out_project(att, lp["attn"])
        if lora_layer is not None:
            dlt = _delta(att.reshape(B, -1), lora_layer, "o", ids_tok,
                         lora_scale)
            if dlt is not None:
                y = y + dlt.reshape(y.shape).astype(y.dtype)
        x = x + y
        h = ll.rms_norm(x, lp["ln2"], cfg.norm_eps)
        if cfg.is_moe:
            y = moe_mod.moe_block(h, lp["moe"], cfg, kind="decode",
                                  lora=lora_layer, ids_tok=ids_tok,
                                  lora_scale=lora_scale)
        else:
            y = _mlp_with_lora(h, lp["mlp"], cfg, lora_layer, ids_tok,
                               lora_scale)
        x = x + y
        return (x, k_all, v_all, l + 1), None

    carry0 = (x, k_cache, v_cache, jnp.int32(0))
    (x, k_cache, v_cache, _), _ = jax.lax.scan(
        body, carry0, (params["layers"], lora_stack))
    x = ll.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = ll.unembed(x, params.get("lm_head", params["embed"]))
    return logits[:, 0], k_cache, v_cache


# --------------------------------------------------------------------- #
# Decode step (one token, all families)                                  #
# --------------------------------------------------------------------- #
def decode_step(params, cfg, cache, tokens, lora_ctx=None):
    """tokens: (B, 1). Returns (logits (B, V), new cache)."""
    fam = cfg.family
    pos = cache["pos"]
    B = tokens.shape[0]
    x = ll.embed(tokens, params["embed"])
    positions = jnp.broadcast_to(pos[None, None], (B, 1)).astype(jnp.int32)

    ids_tok = lora_ctx["ids"] if lora_ctx is not None else None
    lora_scale = lora_ctx["scale"] if lora_ctx is not None else 1.0
    lora_stack = _lora_slice(lora_ctx, ("q", "k", "v", "o", "gate", "up",
                                        "down"))

    if fam in ("dense", "moe", "vlm", "audio"):
        new_cache = dict(cache)
        kv_quant = "k_scale" in cache

        def body(carry, xs):
            x, k_all, v_all, ks_all, vs_all, l = carry
            if fam == "audio":
                lp, ck, cv = xs
                lora_layer = None
            else:
                lp, lora_layer = xs
            h = ll.rms_norm(x, lp["ln1"], cfg.norm_eps)
            q, k, v = ll.qkv_project(h, lp["attn"], cfg)
            if fam != "audio" and lora_layer is not None:
                xf = h.reshape(B, -1)
                for name in ("q", "k", "v"):
                    dlt = _delta(xf, lora_layer, name, ids_tok, lora_scale)
                    if dlt is not None:
                        if name == "q":
                            q = q + dlt.reshape(q.shape).astype(q.dtype)
                        elif name == "k":
                            k = k + dlt.reshape(k.shape).astype(k.dtype)
                        else:
                            v = v + dlt.reshape(v.shape).astype(v.dtype)
            q = ll.apply_rope(q, positions, cfg.rope_theta)
            k = ll.apply_rope(k, positions, cfg.rope_theta)

            def layer_slice(buf):
                return (None if buf is None else
                        jax.lax.dynamic_index_in_dim(buf, l, 0, keepdims=False))

            def layer_write(buf, new):
                return (buf if new is None else
                        jax.lax.dynamic_update_index_in_dim(buf, new, l, 0))

            att, k_c, v_c, ks_c, vs_c, _ = ll.decode_attention_update(
                q[:, 0], k[:, 0], v[:, 0], layer_slice(k_all),
                layer_slice(v_all), pos, window=cfg.sliding_window,
                k_scale=layer_slice(ks_all), v_scale=layer_slice(vs_all))
            k_all = layer_write(k_all, k_c)
            v_all = layer_write(v_all, v_c)
            ks_all = layer_write(ks_all, ks_c)
            vs_all = layer_write(vs_all, vs_c)
            att = att[:, None]  # (B, 1, H, hd)
            y = ll.out_project(att, lp["attn"])
            if fam != "audio" and lora_layer is not None:
                dlt = _delta(att.reshape(B, -1), lora_layer, "o", ids_tok,
                             lora_scale)
                if dlt is not None:
                    y = y + dlt.reshape(y.shape).astype(y.dtype)
            x = x + y
            if fam == "audio":
                h = ll.rms_norm(x, lp["ln2"], cfg.norm_eps)
                cq, _, _ = ll.qkv_project(h, lp["cross"], cfg)
                catt = ll.decode_attention(cq[:, 0], ck, cv,
                                           cache["cross_len"])
                x = x + ll.out_project(catt[:, None], lp["cross"])
                h = ll.rms_norm(x, lp["ln3"], cfg.norm_eps)
                y = ll.mlp(h, lp["mlp"], cfg)
            else:
                h = ll.rms_norm(x, lp["ln2"], cfg.norm_eps)
                if cfg.is_moe:
                    y = moe_mod.moe_block(h, lp["moe"], cfg, kind="decode",
                                          lora=lora_layer, ids_tok=ids_tok,
                                          lora_scale=lora_scale)
                else:
                    y = _mlp_with_lora(h, lp["mlp"], cfg, lora_layer,
                                       ids_tok, lora_scale)
            x = x + y
            return (x, k_all, v_all, ks_all, vs_all, l + 1), None

        if fam == "audio":
            xs = (params["layers"], cache["ck"], cache["cv"])
        else:
            xs = (params["layers"], lora_stack)
        carry0 = (x, cache["k"], cache["v"], cache.get("k_scale"),
                  cache.get("v_scale"), jnp.int32(0))
        carry, _ = jax.lax.scan(body, carry0, xs)
        x = carry[0]
        new_cache["k"], new_cache["v"] = carry[1], carry[2]
        if kv_quant:
            new_cache["k_scale"], new_cache["v_scale"] = carry[3], carry[4]
        new_cache["pos"] = pos + 1

    elif fam == "ssm" and cfg.rwkv:
        new_cache = dict(cache)

        def body(x, xs):
            lp, tm, cm, wkv = xs
            st = ssm.RWKV6State(tm, cm, wkv)
            h = ll.rms_norm(x, lp["ln1"], cfg.norm_eps)
            y, st = ssm.rwkv6_time_mix(h, lp, cfg, st, chunk=1)
            x = x + y
            h = ll.rms_norm(x, lp["ln2"], cfg.norm_eps)
            y, st = ssm.rwkv6_channel_mix(h, lp, cfg, st)
            x = x + y
            return x, (st.shift_tm, st.shift_cm, st.wkv)

        x, states = jax.lax.scan(
            body, x, (params["layers"], cache["tm"], cache["cm"],
                      cache["wkv"]))
        new_cache["tm"], new_cache["cm"], new_cache["wkv"] = states
        new_cache["pos"] = pos + 1

    elif fam == "hybrid":
        new_cache = dict(cache)
        sp = params["shared_attn"]
        W = cache["ak"].shape[2]
        slot = pos % W

        def group(carry, xs):
            x, apos, g = carry
            glp, h_st, conv_st, ak, av = xs
            # shared attention block against the ring-buffer window KV
            h = ll.rms_norm(x, sp["ln1"], cfg.norm_eps)
            q, k, v = ll.qkv_project(h, sp["attn"], cfg)
            q = ll.apply_rope(q, positions, cfg.rope_theta)
            k = ll.apply_rope(k, positions, cfg.rope_theta)
            att, ak, av, _, _, apos = ll.decode_attention_update(
                q[:, 0], k[:, 0], v[:, 0], ak, av, pos,
                window=cfg.sliding_window, key_positions=apos,
                write_slot=slot)
            x = x + ll.out_project(att[:, None], sp["attn"])
            h = ll.rms_norm(x, sp["ln2"], cfg.norm_eps)
            x = x + ll.mlp(h, sp["mlp"], cfg)

            def mstep(x, ms):
                lp, hh, cc = ms
                y, st = ssm.mamba2_decode_step(
                    x, lp, cfg, ssm.Mamba2State(hh, cc))
                return x + y, (st.h, st.conv)

            x, states = jax.lax.scan(mstep, x, (glp, h_st, conv_st))
            return (x, apos, g + 1), (states[0], states[1], ak, av)

        (x, apos, _), aux = jax.lax.scan(
            group, (x, cache["apos"], jnp.int32(0)),
            (params["layers"], cache["h"], cache["conv"], cache["ak"],
             cache["av"]))
        new_cache["h"], new_cache["conv"] = aux[0], aux[1]
        new_cache["ak"], new_cache["av"] = aux[2], aux[3]
        new_cache["apos"] = apos
        new_cache["pos"] = pos + 1
    else:
        raise ValueError(fam)

    x = ll.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = ll.unembed(x, params.get("lm_head", params["embed"]))
    return logits[:, 0], new_cache
