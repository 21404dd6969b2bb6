"""Plain reference of a decoder-only MoE language model with expert LoRA
adapters, in float32 at ``highest`` matmul precision. It imports nothing
of the program under test.

The mathematics, per layer (Mixtral, Qwen3-MoE without q/k norm):

  h   = rmsnorm(x) * (1 + ln1)                      eps from the config
  q,k,v = h Wq, h Wk, h Wv; rotary embedding on q, k (half-split pairs)
  x  += softmax(q k^T / sqrt(hd), causal) v Wo      query head i reads
                                                    key head i // (H / KV)
  h   = rmsnorm(x) * (1 + ln2)
  p   = softmax(h Wr); the top_k experts, weights renormalised to sum 1
  x  += sum_e p_e (silu(h Wg_e + m dG_e) * (h Wu_e + m dU_e)) Wd_e + m dD_e
        with dT_e = s * (in A_T[e]) B_T[e] for the request's adapter,
        s = alpha / rank, and m = 1 from the last prompt token on (the
        served plane prefills without the adapter), else 0
  logits = (rmsnorm(x) * (1 + final_norm)) lm_head^T over the vocabulary

Every expert is computed for every token and weighted by p_e (zero for
an expert not chosen), which is the dropless result exactly.

``quant="fp8"`` is the control: the same computation with every weight
matmul's operands rounded to float8_e4m3fn (weights per output column,
activations per row, each scaled to its largest magnitude); attention's
own products and the softmaxes stay in float32.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0


def _q8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _mm(a, w, quant):
    """a (..., k) @ w (k, n) in float32; fp8-rounded operands for the
    control."""
    a = a.astype(F32)
    w = w.astype(F32)
    if quant == "fp8":
        a, w = _q8(a, -1), _q8(w, 0)
    return jnp.matmul(a, w, precision=HIGHEST)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + g.astype(F32))


def _rope(x, pos, theta):
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (np.arange(0, hd, 2) / hd))
    ang = pos[:, None].astype(F32) * jnp.asarray(freqs, F32)  # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(x, lyr, dims, quant):
    """x + the layer's attention block."""
    (d, H, KV, hd, E, K, eps, theta, scale, window) = dims
    S = x.shape[0]
    att = lyr["attn"]
    h = _rms(x, lyr["ln1"], eps)
    q = _mm(h, att["wq"], quant).reshape(S, H, hd)
    k = _mm(h, att["wk"], quant).reshape(S, KV, hd)
    v = _mm(h, att["wv"], quant).reshape(S, KV, hd)
    pos = jnp.arange(S)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    kv_of = jnp.arange(H) // (H // KV)
    k, v = k[:, kv_of], v[:, kv_of]                       # (S, H, hd)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) / np.sqrt(hd)
    mask = pos[:, None] >= pos[None, :]
    if window:
        mask &= pos[:, None] - pos[None, :] < window
    s = jnp.where(mask[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v,
                   precision=HIGHEST).reshape(S, H * hd)
    return x + _mm(o, att["wo"], quant)


def routed_experts(h, moe, ad, lora_on, dims, quant):
    """The top_k experts' weighted sum over the normed input ``h``, each
    expert with the request's adapter's deltas."""
    (d, H, KV, hd, E, K, eps, theta, scale, window) = dims
    probs = jax.nn.softmax(_mm(h, moe["router"], quant), -1)
    top, ids = jax.lax.top_k(probs, K)
    top = top / jnp.sum(top, -1, keepdims=True)
    p = jnp.sum(jax.nn.one_hot(ids, E, dtype=F32) * top[..., None], 1)
    m = (lora_on.astype(F32) * scale)[:, None]

    def lora(t, e, inp):
        A = ad[t]["A"][e]
        B = ad[t]["B"][e]
        return m * _mm(_mm(inp, A, quant), B, quant)

    def expert(acc, e):
        wg, wu, wd = moe["gate"][e], moe["up"][e], moe["down"][e]
        g = _mm(h, wg, quant) + lora("gate", e, h)
        u = _mm(h, wu, quant) + lora("up", e, h)
        a = jax.nn.silu(g) * u
        y = _mm(a, wd, quant) + lora("down", e, a)
        return acc + jnp.take(p, e, axis=1)[:, None] * y, None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(h), jnp.arange(E))
    return y


@functools.partial(jax.jit, static_argnames=("dims", "quant"))
def _layer(x, lyr, ad, lora_on, dims, quant):
    """One layer, given its own weights and the request's adapter's
    factors for it (``ad[t]["A"]`` is (E, d_in, r))."""
    x = attention(x, lyr, dims, quant)
    h = _rms(x, lyr["ln2"], dims[6])
    return x + routed_experts(h, lyr["moe"], ad, lora_on, dims, quant)


@functools.partial(jax.jit, static_argnames=("eps", "vocab", "quant"))
def _head(x, rows, final_norm, lm_head, eps, vocab, quant):
    h = _rms(x[rows], final_norm, eps)
    w = lm_head[:vocab].astype(F32)
    if quant == "fp8":
        h, w = _q8(h, -1), _q8(w, -1)       # per row, per vocabulary entry
    return jnp.einsum("sd,vd->sv", h, w, precision=HIGHEST)


def dims_key(m: Dict):
    return (m["d_model"], m["n_heads"], m["n_kv_heads"], m["head_dim"],
            m["n_experts"], m["top_k"], m["norm_eps"], m["rope_theta"],
            m["lora_scale"], m["window"])


@jax.jit
def _index(tree, *idx):
    """Each leaf of ``tree`` at the leading indices ``idx``, placed as the
    leaf is."""
    return jax.tree_util.tree_map(lambda a: a[idx], tree)


def _here(tree):
    """``tree``'s arrays on the first device: a leaf placed over several
    chips is gathered there. Callers hand over one layer at a time, so
    that the reference never holds more than one whole layer on a chip
    beside the weights as placed."""
    dev = jax.devices()[0]
    return jax.tree_util.tree_map(lambda a: jax.device_put(a, dev), tree)


def logits(params: Dict, adapters: Dict, m: Dict, tokens: np.ndarray,
           lora_from: int, adapter: int, rows: np.ndarray, *, pad_to: int,
           quant: Optional[str] = None, layer=_layer) -> np.ndarray:
    """Float32 logits (len(rows), vocab) at positions ``rows`` of the
    sequence ``tokens``, with the adapter applied from position
    ``lora_from`` on. The sequence is zero-padded to ``pad_to`` positions
    at the end, which no earlier position sees. The weights may lie on
    one device or be placed over a mesh; each layer is read whole on the
    first device, in turn, and computed by ``layer`` (a reference built
    on this one passes its own)."""
    S = len(tokens)
    tok = np.zeros(pad_to, np.int32)
    tok[:S] = tokens
    x = _here(params["embed"])[jnp.asarray(tok)].astype(F32)
    lora_on = jnp.asarray(np.arange(pad_to) >= lora_from)
    dk = dims_key(m)
    for l in range(m["n_layers"]):
        lyr = _here(_index(params["layers"], jnp.int32(l)))
        ad = _here(_index(adapters, jnp.int32(l), jnp.int32(adapter)))
        x = layer(x, lyr, ad, lora_on, dk, quant)
    out = _head(x, jnp.asarray(rows, jnp.int32), _here(params["final_norm"]),
                _here(params["lm_head"]), m["norm_eps"], m["vocab"], quant)
    return np.asarray(out)
