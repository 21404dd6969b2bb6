"""Plain float32 reference of the toy layout ``shared_expert``: each layer
is ``moe_lm``'s, with the shared expert's output added beside the routed
experts' (no adapter on the shared expert):

  x += routed(h) + (silu(h Wsg) * (h Wsu)) Wsd,   h = rmsnorm(x) * (1 + ln2)
"""
from __future__ import annotations

import functools

import jax

import spec

base = spec.load_module("reference/moe_lm.py")


@functools.partial(jax.jit, static_argnames=("dims", "quant"))
def _layer(x, lyr, ad, lora_on, dims, quant):
    x = base.attention(x, lyr, dims, quant)
    h = base._rms(x, lyr["ln2"], dims[6])
    moe = lyr["moe"]
    shared = base._mm(jax.nn.silu(base._mm(h, moe["shared_gate"], quant))
                      * base._mm(h, moe["shared_up"], quant),
                      moe["shared_down"], quant)
    return x + base.routed_experts(h, moe, ad, lora_on, dims, quant) + shared


def logits(*args, **kw):
    return base.logits(*args, layer=_layer, **kw)
