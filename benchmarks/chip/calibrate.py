#!/usr/bin/env python3
"""Readings that the correctness limit is set from: for each seed, a
short window of the cell at its own rate and sizes, then the program's
served tokens and the fp8 control against the float32 reference, over
the same sample a run compares (check.py). Several seeds in one process,
so that the programs compile once.

  python3 benchmarks/chip/calibrate.py --workload <cell> \
      --seeds 1,2,3,... --seconds 10

Prints one JSON line per seed: gap statistics of the served tokens
(``served``) and of the control's top tokens (``control``), and the
verdict of ``check.decide`` on each against the cell's limit
(``served_correct``, which sound runs must keep true, and
``control_correct``, which must come out false). The lower reading is
the largest ``served.p98`` over the seeds, the upper the smallest
``control.p98``.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import spec  # noqa: E402

sys.path.insert(1, str(spec.CHECKOUT / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)

    import jax
    import run
    if jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU", file=sys.stderr)
        return 2
    run.enable_cache(jax)
    import check
    import e2e
    import harness
    import workload
    bench = spec.load_benchmark()
    cell = spec.find_cell(bench, args.workload)
    conf = spec.load_config(bench, cell["config"])
    traffic = workload.load_traffic(cell["traffic"])
    cellf = spec.load_cell(args.workload)
    rate = cellf["rate_rps"]
    for seed in (int(s) for s in args.seeds.split(",")):
        b = harness.build(conf, traffic, seed)
        harness.warm_up(b, seed)
        reqs = workload.generate(traffic, rate=rate, seconds=args.seconds,
                                 seed=seed, vocab=b.dims["vocab"],
                                 n_adapters=b.dims["n_adapters"])
        rec = harness.drive(b, reqs, args.seconds, run.DRAIN_S)
        harness.release(b)
        picked = check.sample(rec, seed)
        t_ref = time.perf_counter()
        g = check.gaps(b, picked, spec.max_len(conf, b.longest),
                       control=True)
        print(json.dumps({
            "seed": seed, "requests": len(rec.sent),
            "finished": sum(1 for s in rec.sent if e2e.done(s)),
            "compared": int(g["served"].size),
            "reference_and_control_s": time.perf_counter() - t_ref,
            "served": check.stats(g["served"]),
            "control": check.stats(g["control"]),
            "served_correct": check.decide(rec, g["served"],
                                           cellf["check"])["correct"],
            "control_correct": check.decide(rec, g["control"],
                                            cellf["check"])["correct"]}),
            flush=True)
        del b, rec, g
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
