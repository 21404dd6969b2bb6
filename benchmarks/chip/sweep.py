#!/usr/bin/env python3
"""Knee sweep: one cell served at each of a list of offered rates, in one
process on one system, reporting for each rate the share of requests
that met both limits and whether the backlog grew.

  python3 benchmarks/chip/sweep.py --workload <cell> --seed <n> \
      --seconds 20 --rates 2,3,4,5,6,8

The knee is the highest rate at which the backlog does not grow
(requests sent and unfinished at the window's close no more than at its
middle, give or take the batch) and the share of requests that meet both
limits stays at 90 % or more. Where even the lowest rate reads under
100 %, as where the longest prompts miss the first-token limit at any
load, the share may fall no more than 10 points below that rate's. A
cell offers 0.8 of the knee. Rates go in ascending order, and the
sweep stops after the first rate past the knee. Prints one JSON line per
rate, then ``{"knee_rps": ...}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import spec  # noqa: E402

sys.path.insert(1, str(spec.CHECKOUT / "src"))


def pending_at(rec, t: float) -> int:
    """Requests sent by ``t`` and not finished by then."""
    return sum(1 for s in rec.sent if s.sched <= t and
               not (s.stamps and len(s.stamps) == s.req.output_len
                    and s.stamps[-1] <= t))


def sustained(out, max_batch: int, floor: float) -> bool:
    """Whether a rate's line meets the knee's two conditions."""
    return out["slo_attainment"] >= floor and \
        out["pending_at_close"] <= out["pending_at_half"] + max_batch


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)

    import jax
    import run
    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 2
    run.enable_cache(jax)
    import e2e
    import harness
    import workload
    bench = spec.load_benchmark()
    cell = spec.find_cell(bench, args.workload)
    conf = spec.load_config(bench, cell["config"])
    traffic = workload.load_traffic(cell["traffic"])
    b = harness.build(conf, traffic, args.seed)
    harness.warm_up(b, args.seed)
    knee = None
    rates = sorted(float(r) for r in args.rates.split(","))
    for i, rate in enumerate(rates):
        reqs = workload.generate(traffic, rate=rate, seconds=args.seconds,
                                 seed=args.seed + i, vocab=b.dims["vocab"],
                                 n_adapters=b.dims["n_adapters"])
        rec = harness.drive(b, reqs, args.seconds, run.DRAIN_S)
        m = e2e.metrics(rec, 0.0)
        mid = rec.t0 + args.seconds / 2
        out = {"rate_rps": rate, "requests": len(rec.sent),
               "slo_attainment": m["slo_attainment"],
               "ttft_p90_ms": m["ttft_p90_ms"],
               "tpot_p90_ms": m["tpot_p90_ms"],
               "output_tokens_per_s": m["output_tokens_per_s"],
               "pending_at_half": pending_at(rec, mid),
               "pending_at_close": pending_at(rec, rec.t_end),
               "unfinished": sum(1 for s in rec.sent if not e2e.done(s)),
               "drain_s": rec.t_drained - rec.t_end}
        print(json.dumps(out), flush=True)
        while not b.system.backend.idle():
            b.system.step()
        if i == 0:
            floor = min(90.0, out["slo_attainment"] - 10.0)
        if not sustained(out, conf["serve"]["max_batch"], floor):
            break
        knee = rate
    harness.release(b)
    print(json.dumps({"knee_rps": knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
