"""The benchmark's weights and adapters, drawn on the device from the seed
in one compiled call, in the type they are served in (bfloat16), each
leaf where it is served.

The configuration's layout (``layouts/<name>.py``) gives the leaves, in
the order they are drawn, and their placement: with ``serve.mesh_shape``
set, a leaf's expert dimension lies along the mesh's expert axis and
every other leaf is on every chip, so that no chip ever holds more than
its share; without a mesh everything is on the default device. Leaf
``i`` is drawn from ``fold_in(key, i)`` by JAX's partitionable threefry,
so its bits do not depend on where it is placed.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import spec

BF16 = jnp.bfloat16


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of up to 64 bits."""
    key = jax.random.key(0)
    for word in (seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF):
        key = jax.random.fold_in(key, jnp.asarray(np.uint32(word)))
    return key


def nest(flat: Dict) -> Dict:
    out: Dict = {}
    for path, v in flat.items():
        node = out
        *head, last = path.split(".")
        for k in head:
            node = node.setdefault(k, {})
        node[last] = v
    return out


def leaves(conf: Dict) -> Dict:
    """{path: (shape, fan_in, gain_key)} of every leaf in drawing order,
    the adapters' under ``adapters.``."""
    lay = spec.layout(conf)
    w, a = lay.shapes(lay.dims(conf))
    return {**w, **{"adapters." + k: v for k, v in a.items()}}


def mesh_of(conf: Dict) -> Optional[jax.sharding.Mesh]:
    """The serving mesh of ``serve.mesh_shape``: the first data x model
    devices, axes ("data", "model"), as the program lays them out."""
    shape = conf["serve"].get("mesh_shape")
    if not shape:
        return None
    data, model = shape
    devs = jax.devices()
    if data * model > len(devs):
        raise RuntimeError(f"mesh_shape {shape} needs {data * model} "
                           f"devices, {len(devs)} visible")
    return jax.sharding.Mesh(
        np.asarray(devs[:data * model]).reshape(data, model),
        ("data", "model"))


def partition_specs(conf: Dict) -> Dict[str, jax.sharding.PartitionSpec]:
    """{path: PartitionSpec} of every leaf on the configuration's mesh."""
    lay = spec.layout(conf)
    axis = lay.expert_axis(lay.dims(conf), conf["serve"]["mesh_shape"])
    out = {}
    for path, (shape, _, _) in leaves(conf).items():
        dim = lay.expert_dim(path) if axis else None
        parts = [None] * len(shape)
        if dim is not None:
            parts[dim] = axis
        out[path] = jax.sharding.PartitionSpec(*parts)
    return out


def _draw(key, spec):
    out = {}
    for i, (path, shape, std) in enumerate(spec):
        x = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        out[path] = (x * std).astype(BF16)
    return out


_draw_here = jax.jit(_draw, static_argnames=("spec",))


def make(conf: Dict, seed: int) -> Tuple[Dict, Dict]:
    """(params, adapters) from ``seed``, each leaf in its placement."""
    init = conf["init"]
    spec_ = []
    for path, (shape, fan_in, gain) in leaves(conf).items():
        std = init[gain] if fan_in is None else init[gain] / np.sqrt(fan_in)
        spec_.append((path, tuple(shape), float(std)))
    mesh = mesh_of(conf)
    draw = _draw_here if mesh is None else jax.jit(
        _draw, static_argnames=("spec",), out_shardings={
            path: jax.sharding.NamedSharding(mesh, ps)
            for path, ps in partition_specs(conf).items()})
    tree = nest(draw(seed_key(seed), tuple(spec_)))
    return {k: v for k, v in tree.items() if k != "adapters"}, \
        tree["adapters"]


@jax.jit
def _leaf_sum(x):
    bits = jax.lax.bitcast_convert_type(x, jnp.uint16).reshape(-1)
    w = jax.lax.iota(jnp.uint32, bits.size) % jnp.uint32(65521) + 1
    return jnp.sum(bits.astype(jnp.uint32) * w, dtype=jnp.uint32)


def flatten(params: Dict, adapters: Optional[Dict] = None
            ) -> Dict[str, jax.Array]:
    """{path: leaf} of a draw, under the paths of ``leaves``."""
    out = {}
    for prefix, tree in (("", params), ("adapters.", adapters or {})):
        for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
            out[prefix + ".".join(str(k.key) for k in path)] = x
    return out


def checksum(params: Dict, adapters: Dict) -> Dict[str, int]:
    """{path: 32-bit checksum of a bfloat16 leaf's bits}: each 16-bit word
    times its flat index mod 65521, plus 1, summed modulo 2**32."""
    return {k: int(_leaf_sum(x))
            for k, x in flatten(params, adapters).items()}
