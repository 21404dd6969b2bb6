"""The mesh checks of test_bench_mesh.py, in a process of their own: the
CPU backend is given four devices before JAX starts, which a process
that has already started JAX cannot do.

  XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
      python3 benchmarks/chip/tests/mesh_worker.py

Prints one JSON object: what each check read.
"""
from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import tiny  # noqa: E402  (puts the benchmark and the program on sys.path)

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

import spec  # noqa: E402
import weights  # noqa: E402

SEED = 2**31 + 99
MESH = [4, 1]


def on_mesh(conf):
    return dict(conf, serve=dict(conf["serve"], mesh_shape=MESH))


def device_bytes(tree):
    """{device id: bytes of the pieces of ``tree`` on that device}."""
    out = {}
    for x in jax.tree_util.tree_leaves(tree):
        for sh in x.addressable_shards:
            out[sh.device.id] = out.get(sh.device.id, 0) + sh.data.nbytes
    return out


def draw_checks(conf):
    one = weights.make(conf, SEED)
    placed = weights.make(on_mesh(conf), SEED)
    lay = spec.layout(conf)
    a, b = weights.flatten(*one), weights.flatten(*placed)
    return one, placed, {
        "device_bytes": device_bytes(placed),
        "replicated_bytes": sum(x.nbytes for k, x in b.items()
                                if lay.expert_dim(k) is None),
        "expert_bytes": sum(x.nbytes for k, x in b.items()
                            if lay.expert_dim(k) is not None),
        "bitwise_equal": {k: bool(np.array_equal(
            np.asarray(a[k]).view(np.uint16),
            np.asarray(b[k]).view(np.uint16))) for k in a},
        "specs": {k: str(x.sharding.spec) for k, x in b.items()},
        "checksums_equal": weights.checksum(*one) == weights.checksum(*placed),
    }


def program_placement(conf, placed):
    """Whether the program's own placement of the drawn weights
    (``shard_serve_params``) puts each leaf where the draw did."""
    from repro.distributed.steps import (expert_parallel_ctx,
                                         shard_serve_params)
    from repro.launch.mesh import make_serve_mesh
    cfg = spec.layout(conf).model_config(conf)
    ctx = expert_parallel_ctx(make_serve_mesh(*MESH), cfg)
    ours = weights.flatten(placed[0])
    theirs = weights.flatten(shard_serve_params(dict(placed[0]), ctx))
    return {k: bool(x.sharding.is_equivalent_to(theirs[k].sharding, x.ndim))
            for k, x in ours.items()}


def reference_checks(conf, one, placed):
    ref = spec.load_module(conf["reference"])
    m = spec.layout(conf).dims(conf)
    rng = np.random.default_rng(SEED)
    toks = rng.integers(0, m["vocab"], 40)
    rows = np.arange(20, 40)
    a = ref.logits(*one, m, toks, 24, 1, rows, pad_to=64)
    b = ref.logits(*placed, m, toks, 24, 1, rows, pad_to=64)
    return {"max_abs_diff": float(np.max(np.abs(a - b))),
            "max_abs_logit": float(np.max(np.abs(a)))}


def cell_run(conf):
    with pytest.MonkeyPatch.context() as mp:
        tiny.install(mp, limit=0.05)
        mp.setattr(spec, "load_config", lambda b, name: on_mesh(conf))
        import run
        args = run.parse(["--workload", tiny.CELL, "--seed", str(SEED),
                          "--seconds", "3", "--trace", "0"])
        res = run.execute(args, check_device=False)
    return {"correct": res["correct"], "checks": res["checks"],
            "attempted": res["attempted"], "failed": res["failed"]}


def main() -> int:
    conf = tiny._read("tiny.json")
    out = {"devices": len(jax.devices())}
    one, placed, out["draw"] = draw_checks(conf)
    out["program_placement"] = program_placement(conf, placed)
    out["reference"] = reference_checks(conf, one, placed)
    del one, placed
    out["cell"] = cell_run(conf)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
