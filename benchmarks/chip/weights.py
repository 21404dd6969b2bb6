"""The benchmark's weights and adapters, drawn on the device from the seed
in one compiled call, in the type they are served in (bfloat16).

Layout (shared by the program and the reference; the benchmark owns it):

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
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from spec import model_dims

BF16 = jnp.bfloat16


def padded_vocab(vocab: int) -> int:
    return -(-vocab // 256) * 256


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of up to 64 bits."""
    key = jax.random.key(0)
    for word in (seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF):
        key = jax.random.fold_in(key, jnp.asarray(np.uint32(word)))
    return key


def shapes(m: Dict) -> Tuple[Dict, Dict]:
    """{path: (shape, fan_in, gain_key)} of the weights and the adapters."""
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


def nest(flat: Dict) -> Dict:
    out: Dict = {}
    for path, v in flat.items():
        node = out
        *head, last = path.split(".")
        for k in head:
            node = node.setdefault(k, {})
        node[last] = v
    return out


@functools.partial(jax.jit, static_argnames=("spec",))
def _draw(key, spec):
    out = {}
    for i, (path, shape, std) in enumerate(spec):
        x = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        out[path] = (x * std).astype(BF16)
    return out


def make(conf: Dict, seed: int) -> Tuple[Dict, Dict]:
    """(params, adapters) on the default device, from ``seed``."""
    m = model_dims(conf)
    init = conf["init"]
    w, a = shapes(m)
    spec = []
    for path, (shape, fan_in, gain) in list(w.items()) + [
            ("adapters." + k, v) for k, v in a.items()]:
        std = init[gain] if fan_in is None else init[gain] / np.sqrt(fan_in)
        spec.append((path, tuple(shape), float(std)))
    flat = _draw(seed_key(seed), tuple(spec))
    tree = nest(flat)
    return {k: v for k, v in tree.items() if k != "adapters"}, \
        tree["adapters"]
