#!/usr/bin/env python3
"""One run of one benchmark cell on the chip.

  python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
      --seconds <s> --trace <0|1>

Builds the cell's model from its configuration file with weights and
adapters drawn on the device from ``--seed``, serves the cell's traffic as
an open loop on the wall clock for ``--seconds`` (see harness.py), checks
the served tokens against the plain reference (check.py), and prints one
JSON line last on standard output: the end-to-end metrics with
``--trace 0``, the per-layer metrics with ``--trace 1``. Exits nonzero,
printing no result, when the first device is not a TPU or fewer chips are
visible than the cell asks for.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import spec  # noqa: E402

sys.path.insert(1, str(spec.CHECKOUT / "src"))

CACHE_DIR = spec.CHECKOUT / ".jax_compile_cache"
OUT_DIR = spec.CHECKOUT / ".bench_out"
DRAIN_S = 60.0
TRACE_S = 6.0


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def enable_cache(jax) -> None:
    """JAX's persistent compilation cache, every program cached, so only
    a checkout's first run compiles: in ``$JAX_COMPILATION_CACHE_DIR``
    where that is set, else at one fixed directory inside the
    checkout."""
    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR")
                      or str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def device_info(jax, n_chips: int, need: int):
    devs = jax.devices()
    if devs[0].platform != "tpu":
        log(f"run: the first device is {devs[0].platform!r}, not a TPU")
        return None
    if len(devs) < need:
        log(f"run: the cell needs {need} chips, {len(devs)} visible")
        return None
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def layer_metrics(bench, cell_name, ctx):
    import layerctx
    e2e = {m["name"] for m in bench["end_to_end"]
           if "workloads" not in m or cell_name in m["workloads"]}
    out = {}
    for entry in bench["per_layer"]:
        if not layerctx.applies(entry, cell_name, e2e):
            continue
        rd = layerctx.load_reader(entry["name"])
        declared = (rd.LAYER, rd.UNIT, rd.SOURCE, rd.MOVES)
        listed = (entry["layer"], entry["unit"], entry["source"],
                  entry["moves"])
        if declared != listed:
            raise RuntimeError(f"metric {entry['name']}: reader declares "
                               f"{declared}, BENCHMARK.json {listed}")
        v = rd.read(ctx)
        if v is not None:
            out[entry["name"]] = {"value": v, "unit": entry["unit"]}
    return out


def execute(args, check_device=True):
    """The whole run; returns the result object, or None without a
    chip."""
    bench = spec.load_benchmark()
    cell = spec.find_cell(bench, args.workload)
    conf = spec.load_config(bench, cell["config"])
    import workload
    traffic = workload.load_traffic(cell["traffic"])
    cellf = spec.load_cell(args.workload)

    import jax
    if check_device:
        dev = device_info(jax, spec.chips_of(conf), cell["chips"])
        if dev is None:
            return None
        enable_cache(jax)
    else:
        d0 = jax.devices()[0]
        dev = {"platform": d0.platform, "kind": d0.device_kind,
               "count": len(jax.devices())}

    import check
    import e2e as e2e_mod
    import harness
    import peaks
    peak = peaks.peak(dev["kind"]) if check_device else None

    b = harness.build(conf, traffic, args.seed)
    harness.warm_up(b, args.seed)
    reqs = workload.generate(traffic, rate=cellf["rate_rps"],
                             seconds=args.seconds, seed=args.seed,
                             vocab=b.dims["vocab"],
                             n_adapters=b.dims["n_adapters"])
    trace_dir = None
    if args.trace:
        trace_dir = str(OUT_DIR / "trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
    rec = harness.drive(b, reqs, args.seconds, DRAIN_S, trace_dir=trace_dir,
                        trace_s=min(TRACE_S, args.seconds / 2))
    setup_s = rec.t0 - T_PROCESS
    chips = spec.chips_of(conf)
    dev["memory_peak_bytes"] = harness.memory_peak_bytes(chips) \
        if check_device else 0
    late = e2e_mod.generator_lateness_ms(rec)
    log(f"generator lateness ms: p50 {late['p50']:.3f} p90 "
        f"{late['p90']:.3f} max {late['max']:.3f}")
    log(f"compiles inside the window: {rec.compiles_in_window}")
    log(f"rounds {len(rec.rounds)}, requests {len(rec.sent)}, window "
        f"{rec.t_end - rec.t0:.3f} s, drained "
        f"{rec.t_drained - rec.t_end:.3f} s after the close")
    log(f"memory peak_bytes_in_use {dev['memory_peak_bytes']}")

    result = {"correct": False, "attempted": len(rec.sent),
              "failed": sum(1 for s in rec.sent if not e2e_mod.done(s))}
    breakdown = None
    if args.trace:
        import layerctx
        import trace_reduce
        path = trace_reduce.find_xplane(trace_dir)
        tr = trace_reduce.load(path) if path else None
        ctx = layerctx.LayerContext(rec, b.dims, peak, chips, tr,
                                    b.layout)
        metrics = layer_metrics(bench, args.workload, ctx)
        if tr is not None and tr.devices:
            lo, hi = trace_reduce.window(tr)
            span = rec.trace_span
            dev["busy_s"] = sum(trace_reduce.busy_seconds(ln) for ln in
                                tr.devices.values()) / len(tr.devices)
            dev["window_s"] = span[1] - span[0]
            breakdown = trace_reduce.breakdown(tr, lo, hi)
        if args.keep_trace and path:
            os.makedirs(args.keep_trace, exist_ok=True)
            shutil.copy(path, args.keep_trace)
    else:
        metrics = {k: {"value": v, "unit": u} for (k, v), u in zip(
            e2e_mod.metrics(rec, setup_s).items(),
            ("ms", "ms", "tokens/s", "%", "s"))}
        if check_device:
            for k, v in metrics.items():
                log(f"metric {k} {v['value']!r} {v['unit']}")

    harness.release(b)
    t_ref = time.perf_counter()
    picked = check.sample(rec, args.seed)
    g = check.gaps(b, picked, spec.max_len(conf, b.longest))
    verdict = check.decide(rec, g["served"], cellf["check"])
    log(f"reference over {len(picked)} requests, {g['served'].size} served "
        f"tokens: {time.perf_counter() - t_ref:.3f} s; gap stats "
        f"{check.stats(g['served'])}")
    result["correct"] = verdict["correct"]
    result["metrics"] = metrics
    result["device"] = dev
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = verdict["checks"]
    for k, c in verdict["checks"].items():
        log(f"check {k} {c['value']!r} {c['rule']} limit {c['limit']!r}")
    return result


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the traced run's .xplane.pb to this directory")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    result = execute(parse(argv))
    if result is None:
        return 2
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
