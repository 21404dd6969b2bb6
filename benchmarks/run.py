"""One module per paper table/figure. Prints ``name,value,derived`` CSV.

  python benchmarks/run.py [filter]         # full sweep (or one module)
  python benchmarks/run.py --smoke          # tiny shapes, <= 60 s, writes
                                            # BENCH_smoke.json (CI artifact)
"""
import argparse
import json
import os
import sys
import time

# allow ``python benchmarks/run.py`` from the repo root (or anywhere),
# with or without PYTHONPATH=src
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_ROOT, "src"))
sys.path.insert(0, _ROOT)

from benchmarks import (bench_ablation, bench_adapter_memory,  # noqa: E402
                        bench_adapters, bench_autoscaler, bench_batch_sweep,
                        bench_cache_ratio, bench_e2e_serving, bench_kernels,
                        bench_observability, bench_parallelism,
                        bench_provisioning, bench_roofline,
                        bench_scale_instances, bench_scale_server,
                        bench_transport, common)
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

ALL = [
    ("fig1a_adapter_memory", bench_adapter_memory.main),
    ("table1_table4_parallelism", bench_parallelism.main),
    ("alg1_provisioning", bench_provisioning.main),
    ("autoscaler_load_shift", bench_autoscaler.main),
    ("fig16_batch_sweep", bench_batch_sweep.main),
    ("fig19_kernels", bench_kernels.main),
    ("fig5_fig6_cache_ratio", bench_cache_ratio.main),
    ("fig14_ablation", bench_ablation.main),
    ("fig12_scale_instances", bench_scale_instances.main),
    ("fig13_scale_server", bench_scale_server.main),
    ("fig11_e2e_serving", bench_e2e_serving.main),
    ("transport_planes", bench_transport.main),
    ("roofline_table", bench_roofline.main),
    ("adapter_store_prefetch", bench_adapters.main),
    ("observability_overhead", bench_observability.main),
]

# CI smoke set: analytic tables (instant) + the real slot-engine cluster on
# tiny shapes — enough to start a perf trajectory without burning CI minutes.
SMOKE = [
    ("fig1a_adapter_memory", bench_adapter_memory.main),
    ("roofline_table", bench_roofline.main),
    ("e2e_cluster_engine", lambda: bench_e2e_serving.cluster_main(
        smoke=True)),
]

# CI provisioning lane: the offline Algorithm-1 numbers plus the online
# autoscaler load-shift scenario (static vs elastic SLO attainment and the
# scaling trajectory) — writes BENCH_provisioning.json as an artifact so the
# provisioning trajectory accumulates per commit.
PROVISIONING = [
    ("alg1_provisioning", bench_provisioning.main),
    ("autoscaler_load_shift", bench_autoscaler.main),
]

# CI transport lane: host-mediated vs GPU-initiated hook transport on the
# real smoke cluster (per-step latency + measured dispatch counts + token
# equality), the fused Pallas kernel's interpret check, and the analytic
# launch-tail pricing — writes BENCH_transport.json as an artifact.
TRANSPORT = [
    ("transport_planes", bench_transport.main),
]

# CI parallelism lane: the analytic Table-1/Table-4 strategy metrics plus
# real sharded execution — each placement runs the mesh-sharded decode step
# (ServeConfig.mesh_shape) on forced host devices and reports scaling rows
# (ms/token, token equality, fused dispatch rate) keyed to the same
# EPx-PPy labels — writes BENCH_parallelism.json as an artifact.
PARALLELISM = [
    ("table1_table4_parallelism", bench_parallelism.main),
    ("real_sharded_scaling", bench_parallelism.real_main),
]

# CI adapter-store lane: the hierarchical store sweep (prefetch on/off over
# a half-host-budget tier under zipf skew) — p95 TTFT, staging counters,
# and the strict_win acceptance rows land in BENCH_adapters.json.
ADAPTERS = [
    ("adapter_store_prefetch", bench_adapters.main),
]

# CI kernels lane: Fig-19 kernel characterization — true-rank modeled
# pricing for the mixed-rank pool, the Pallas interpret checks, and the
# padded-vs-rank-grouped comparison (fig19.rank.*: modeled FLOP reduction,
# interpret wall-time win, bit-identity) — writes BENCH_kernels.json.
KERNELS = [
    ("fig19_kernels", bench_kernels.main),
]

# CI observability lane: tracing overhead on the real smoke cluster
# (NullTracer vs TimelineTracer per-step wall time, <5% acceptance, token
# bit-identity) plus the traced faithfulness run (Perfetto export with
# >=95% TTFT span coverage) — writes BENCH_observability.json and the
# trace_observability.json Perfetto artifact.
OBSERVABILITY = [
    ("observability_overhead", bench_observability.main),
]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("only", nargs="?", default=None,
                    help="substring filter on benchmark names")
    lane = ap.add_mutually_exclusive_group()
    lane.add_argument("--smoke", action="store_true",
                      help="tiny-shape subset (<= 60 s) + JSON artifact")
    lane.add_argument("--provisioning", action="store_true",
                      help="Algorithm-1 + autoscaler load-shift lane, "
                           "writes BENCH_provisioning.json")
    lane.add_argument("--transport", action="store_true",
                      help="host vs fused hook-transport lane, writes "
                           "BENCH_transport.json")
    lane.add_argument("--parallelism", action="store_true",
                      help="analytic Table-1 metrics + real mesh-sharded "
                           "scaling rows, writes BENCH_parallelism.json")
    lane.add_argument("--adapters", action="store_true",
                      help="hierarchical adapter store prefetch sweep, "
                           "writes BENCH_adapters.json")
    lane.add_argument("--kernels", action="store_true",
                      help="Fig-19 kernel lane incl. rank-aware interpret "
                           "checks, writes BENCH_kernels.json")
    lane.add_argument("--observability", action="store_true",
                      help="tracing overhead + Perfetto faithfulness lane, "
                           "writes BENCH_observability.json")
    ap.add_argument("--out", default=None,
                    help="write captured rows as JSON (default "
                         "BENCH_smoke.json in --smoke mode)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    suite = SMOKE if args.smoke else \
        PROVISIONING if args.provisioning else \
        TRANSPORT if args.transport else \
        PARALLELISM if args.parallelism else \
        ADAPTERS if args.adapters else \
        KERNELS if args.kernels else \
        OBSERVABILITY if args.observability else ALL
    timings = {}
    for name, fn in suite:
        if args.only and args.only not in name:
            continue
        t0 = time.time()
        print(f"# === {name} ===", flush=True)
        fn()
        timings[name] = round(time.time() - t0, 2)
        print(f"# {name} done in {timings[name]:.1f}s", flush=True)

    out_path = args.out or ("BENCH_smoke.json" if args.smoke else
                            "BENCH_provisioning.json" if args.provisioning
                            else "BENCH_transport.json" if args.transport
                            else "BENCH_parallelism.json" if args.parallelism
                            else "BENCH_adapters.json" if args.adapters
                            else "BENCH_kernels.json" if args.kernels
                            else "BENCH_observability.json"
                            if args.observability else None)
    if out_path:
        with open(out_path, "w") as f:
            json.dump({"results": common.RESULTS, "timings": timings,
                       "provenance": common.provenance()}, f,
                      indent=1)
        print(f"# wrote {len(common.RESULTS)} rows -> {out_path}",
              flush=True)


if __name__ == '__main__':
    main()
