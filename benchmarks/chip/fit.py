#!/usr/bin/env python3
"""Compile a configuration's served programs for a described TPU v5e 2x2
and print, for each chip, the bytes that stay resident and the
temporaries of each program: the fused decode step at each decode bucket
up to ``max_batch``, and the prefill chunk at each context the longest
prompt reaches. A configuration with ``serve.mesh_shape`` is compiled
over that mesh of the described chips, each leaf placed as the harness
draws it (weights.py). Nothing runs; no chip is needed.

  JAX_PLATFORMS=cpu python3 benchmarks/chip/fit.py \
      --config mixtral-8x7b-disagg --traffic chat [--max-batch 32] \
      [--layers 8]

``--config`` is a configuration of BENCHMARK.json or the path of a
configuration file. Resident, per chip: the weights as placed, the
adapter pool as placed, the LoRA server's slot pool (on the first chip),
the fused transport's view of it (on every chip), the paged KV (on every
chip); and ``program_placement``, what the program's own placement of
the weights adds today (``distributed/steps.shard_serve_params`` puts
every leaf on every chip before it places the experts: for a moment each
chip holds every weight, and it keeps its own placed copy of the experts
beside the drawn one). A layout that the program cannot serve gets the
resident bytes of its weights, adapters and KV, no compile, and exit
code 3.
"""
from __future__ import annotations

import argparse
import functools
import json
import math
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import spec  # noqa: E402

sys.path.insert(1, str(spec.CHECKOUT / "src"))

CANNOT_SERVE = 3


def load(name: str):
    if name.endswith(".json"):
        with open(name) as f:
            return json.load(f)
    return spec.load_config(spec.load_benchmark(), name)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", default="chat")
    ap.add_argument("--max-batch", type=int, default=None)
    ap.add_argument("--prefill-chunk", type=int, default=None)
    ap.add_argument("--layers", type=int, default=None,
                    help="num_hidden_layers in place of the file's")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.kernels import ops
    from repro.models import transformer
    from repro.models.model import abstract_params
    from repro.transport import fused
    import weights
    import workload

    jax.config.update("jax_enable_compilation_cache", False)
    # compile the Mosaic kernels for the chip, not the CPU fallbacks
    ops.kernels_enabled = lambda: True
    ops.pallas_interpret = lambda: False

    conf = load(args.config)
    if args.max_batch:
        conf["serve"]["max_batch"] = args.max_batch
    if args.prefill_chunk:
        conf["serve"]["prefill_chunk"] = args.prefill_chunk
    if args.layers:
        conf["config"]["num_hidden_layers"] = args.layers
    traffic = workload.load_traffic(args.traffic)
    longest = workload.longest_request(traffic)
    lay = spec.layout(conf)
    m = lay.dims(conf)
    cfg = lay.model_config(conf)
    shape = conf["serve"].get("mesh_shape") or [1, 1]
    chips = shape[0] * shape[1]
    axes = dict(zip(("data", "model"), shape))
    specs = weights.partition_specs(dict(
        conf, serve=dict(conf["serve"], mesh_shape=shape)))
    drawn = {k: (v[0], specs[k]) for k, v in weights.leaves(conf).items()}

    def per_chip(leaf_shape, ps=P(), itemsize=2):
        """Bytes of one chip's piece of a leaf placed by ``ps``."""
        split = 1
        for part in ps:
            for ax in (part if isinstance(part, tuple) else (part,)):
                split *= axes.get(ax, 1)
        return math.prod(leaf_shape) * itemsize // split

    ps = conf["serve"]["page_size"]
    nb = spec.max_len(conf, longest) // ps
    B_max = conf["serve"]["max_batch"]
    L, KV, hd = m["n_layers"], m["n_kv_heads"], m["head_dim"]
    kv_shape = (L, B_max * nb, ps, KV, hd)
    out = {"chips": chips, "max_batch": B_max, "resident": {}}
    resident = {
        "weights": sum(per_chip(*v) for k, v in drawn.items()
                       if not k.startswith("adapters.")),
        "adapter_pool": sum(per_chip(*v) for k, v in drawn.items()
                            if k.startswith("adapters.")),
        "paged_kv": 2 * per_chip(kv_shape)}

    want = jax.tree_util.tree_map(lambda a: a.shape, abstract_params(cfg))
    got = weights.nest({k: tuple(v[0]) for k, v in drawn.items()
                        if not k.startswith("adapters.")})
    if want != got:
        out["resident"] = {"0": dict(resident, total=sum(resident.values()))}
        out["program_serves"] = False
        print(f"fit: the program cannot serve layout {conf['layout']}: its "
              f"weights are {got}, the program's {want}; resident bytes "
              f"reckoned, nothing compiled", file=sys.stderr)
        print(json.dumps(out))
        return CANNOT_SERVE

    devs = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices
    mesh = Mesh(np.asarray(devs[:chips]).reshape(shape), ("data", "model"))
    repl = NamedSharding(mesh, P())

    def s(shape, dtype=jnp.bfloat16, sharding=repl):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sharding)

    params = weights.nest({k: s(v[0], sharding=NamedSharding(mesh, v[1]))
                           for k, v in drawn.items()
                           if not k.startswith("adapters.")})
    pool = s(kv_shape)
    ctx = None
    if chips > 1:
        from repro.distributed.steps import expert_parallel_ctx
        ctx = expert_parallel_ctx(mesh, cfg)
    M = m["n_adapters"]
    views = lay.lora_view_shapes(m)
    view = fused.DeviceLoraView(*(s(v) for v in views.values()),
                                s((16,), jnp.int32), s((1, M), jnp.int32))
    view_bytes = sum(per_chip(v) for v in views.values())
    for chip in range(chips):
        r = dict(resident, fused_view=view_bytes,
                 server_slots=view_bytes if chip == 0 else 0)
        r["total"] = sum(r.values())
        out["resident"][str(chip)] = r
    if ctx is not None:
        out["program_placement"] = {
            "every_weight_on_every_chip": sum(
                per_chip(v[0]) for k, v in drawn.items()
                if not k.startswith("adapters.")),
            "program_copy_of_experts": sum(
                per_chip(*v) for k, v in drawn.items()
                if lay.expert_dim(k) is not None
                and not k.startswith("adapters."))}

    def mem(compiled):
        ma = compiled.memory_analysis()
        return {k: int(getattr(ma, k)) for k in (
            "temp_size_in_bytes", "argument_size_in_bytes",
            "output_size_in_bytes", "alias_size_in_bytes")}

    step = jax.jit(functools.partial(fused._fused_paged_fn, mesh_ctx=ctx),
                   static_argnames=("cfg",), donate_argnums=(2, 3))
    out["decode"], out["prefill"] = {}, {}
    B = 1
    while B <= B_max:
        c = step.lower(
            params, cfg, pool, pool, s((B, nb), jnp.int32),
            s((B, 1), jnp.int32), s((B,), jnp.int32), view,
            s((B,), jnp.int32), s((), jnp.float32)).compile()
        out["decode"][B] = mem(c)
        print(f"decode bucket {B}: {out['decode'][B]}", file=sys.stderr,
              flush=True)
        B *= 2
    C = -(-conf["serve"]["prefill_chunk"] // ps) * ps
    for ctx_len in range(0, longest["prompt"] - 1, C):
        c = jax.jit(transformer.prefill_chunk,
                    static_argnames=("cfg",)).lower(
            params, cfg, s((1, C), jnp.int32),
            s((L, 1, ctx_len, KV, hd)), s((L, 1, ctx_len, KV, hd))).compile()
        out["prefill"][ctx_len] = mem(c)
        print(f"prefill chunk {C} at context {ctx_len}: "
              f"{out['prefill'][ctx_len]}", file=sys.stderr, flush=True)
    out["prefill_chunk"] = C
    out["program_serves"] = True
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
