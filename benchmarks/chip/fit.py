#!/usr/bin/env python3
"""Compile a configuration's served programs for a described TPU v5e and
print their ``memory_analysis()``: the fused decode step at each decode
bucket up to ``max_batch`` and the prefill chunk at each context the
longest prompt reaches, beside the bytes that stay resident (weights,
adapter pool, server slot pool, the fused view's copy of it, paged KV).
Nothing runs; no chip is needed.

  JAX_PLATFORMS=cpu python3 benchmarks/chip/fit.py \
      --config mixtral-8x7b-disagg --traffic chat [--max-batch 32]
"""
from __future__ import annotations

import argparse
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import spec  # noqa: E402

sys.path.insert(1, str(spec.CHECKOUT / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", default="chat")
    ap.add_argument("--max-batch", type=int, default=None)
    ap.add_argument("--prefill-chunk", type=int, default=None)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from repro.kernels import ops
    from repro.models import transformer
    from repro.transport import fused
    import weights
    import workload

    jax.config.update("jax_enable_compilation_cache", False)
    # compile the Mosaic kernels for the chip, not the CPU fallbacks
    ops.kernels_enabled = lambda: True
    ops.pallas_interpret = lambda: False

    bench = spec.load_benchmark()
    conf = spec.load_config(bench, args.config)
    if args.max_batch:
        conf["serve"]["max_batch"] = args.max_batch
    if args.prefill_chunk:
        conf["serve"]["prefill_chunk"] = args.prefill_chunk
    traffic = workload.load_traffic(args.traffic)
    longest = workload.longest_request(traffic)
    m = spec.model_dims(conf)
    cfg = spec.model_config(conf)
    dev = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=dev)

    wshape, ashape = weights.shapes(m)
    nest = weights.nest
    params = nest({k: s(v[0]) for k, v in wshape.items()})
    L, E, d, ff, r = m["n_layers"], m["n_experts"], m["d_model"], m["d_ff"], \
        m["rank"]
    M = m["n_adapters"]
    view = fused.DeviceLoraView(
        s((1, L, M, E, d, 2 * r)), s((1, L, M, E, 2 * r, 2 * ff)),
        s((1, L, M, E, ff, r)), s((1, L, M, E, r, d)),
        s((16,), jnp.int32), s((1, M), jnp.int32))
    ps = conf["serve"]["page_size"]
    max_len = spec.max_len(conf, longest)
    nb = max_len // ps
    B_max = conf["serve"]["max_batch"]
    n_pages = B_max * nb
    KV, hd = m["n_kv_heads"], m["head_dim"]
    pool = s((L, n_pages, ps, KV, hd))

    def mem(compiled):
        ma = compiled.memory_analysis()
        return {k: int(getattr(ma, k)) for k in (
            "temp_size_in_bytes", "argument_size_in_bytes",
            "output_size_in_bytes", "alias_size_in_bytes")}

    out = {"decode": {}, "prefill": {}}
    B = 1
    while B <= B_max:
        c = jax.jit(fused._fused_paged_fn, static_argnames=("cfg",),
                    donate_argnums=(2, 3)).lower(
            params, cfg, pool, pool, s((B, nb), jnp.int32),
            s((B, 1), jnp.int32), s((B,), jnp.int32), view,
            s((B,), jnp.int32), s((), jnp.float32)).compile()
        out["decode"][B] = mem(c)
        print(f"decode bucket {B}: {out['decode'][B]}", file=sys.stderr,
              flush=True)
        B *= 2
    C = -(-conf["serve"]["prefill_chunk"] // ps) * ps
    for ctx in range(0, longest["prompt"] - 1, C):
        c = jax.jit(transformer.prefill_chunk,
                    static_argnames=("cfg",)).lower(
            params, cfg, s((1, C), jnp.int32), s((L, 1, ctx, KV, hd)),
            s((L, 1, ctx, KV, hd))).compile()
        out["prefill"][ctx] = mem(c)
        print(f"prefill chunk {C} at context {ctx}: {out['prefill'][ctx]}",
              file=sys.stderr, flush=True)

    def nbytes(tree):
        return sum(int(x.size) * x.dtype.itemsize
                   for x in jax.tree_util.tree_leaves(tree))
    pool_bytes = nbytes(nest({k: s(v[0]) for k, v in ashape.items()}))
    view_bytes = nbytes([view.up_A, view.up_B, view.down_A, view.down_B])
    out["resident"] = {
        "weights": nbytes(params), "adapter_pool": pool_bytes,
        "server_slots": view_bytes, "fused_view": view_bytes,
        "paged_kv": 2 * nbytes(pool)}
    out["resident"]["total"] = sum(out["resident"].values())
    out["max_batch"], out["prefill_chunk"] = B_max, C
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
