#!/usr/bin/env python3
"""Draw a configuration's weights and adapters from a seed as a run does
(weights.py), and print what each chip then holds and a checksum of every
leaf; with ``--reference``, also run the configuration's reference over
one sequence as long as the traffic's longest request, and print its
time and each chip's peak after it. One JSON line on standard output.

  python3 benchmarks/chip/drawcheck.py --config <name or .json path> \
      --seed <n> [--layers 8] [--reference]

Two trees draw the same bits when their checksums agree; a configuration
with ``serve.mesh_shape`` needs as many chips as the mesh has.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import spec  # noqa: E402

sys.path.insert(1, str(spec.CHECKOUT / "src"))


def memory(jax) -> dict:
    out = {}
    for d in jax.devices():
        st = d.memory_stats() or {}
        out[str(d.id)] = {k: st.get(k) for k in ("bytes_in_use",
                                                 "peak_bytes_in_use")}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--layers", type=int, default=None,
                    help="num_hidden_layers in place of the file's")
    ap.add_argument("--traffic", default="chat")
    ap.add_argument("--reference", action="store_true")
    args = ap.parse_args(argv)

    import jax
    import numpy as np
    import run
    import weights
    import workload
    run.enable_cache(jax)
    if args.config.endswith(".json"):
        with open(args.config) as f:
            conf = json.load(f)
    else:
        conf = spec.load_config(spec.load_benchmark(), args.config)
    if args.layers:
        conf["config"]["num_hidden_layers"] = args.layers
    d0 = jax.devices()[0]
    out = {"device": {"platform": d0.platform, "kind": d0.device_kind,
                      "count": len(jax.devices())}}
    t = time.perf_counter()
    params, adapters = weights.make(conf, args.seed)
    jax.block_until_ready((params, adapters))
    out["draw_s"] = time.perf_counter() - t
    out["after_draw"] = memory(jax)
    out["checksum"] = weights.checksum(params, adapters)
    if args.reference:
        m = spec.layout(conf).dims(conf)
        longest = workload.longest_request(workload.load_traffic(
            args.traffic))
        n = longest["prompt"] + longest["output"]
        toks = np.random.default_rng(args.seed).integers(0, m["vocab"], n)
        rows = np.arange(longest["prompt"] - 1, n - 1)
        ref = spec.load_module(conf["reference"])
        for label in ("reference_first_s", "reference_s"):
            t = time.perf_counter()
            ref.logits(params, adapters, m, toks, longest["prompt"] - 1, 0,
                       rows, pad_to=spec.max_len(conf, longest))
            out[label] = time.perf_counter() - t
        out["after_reference"] = memory(jax)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
