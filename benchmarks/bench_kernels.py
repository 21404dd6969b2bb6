"""Fig 19: LoRA kernel characterization across shrink (d->r) and expand
(r->d) phases — BGMV vs SGMV vs the fused shrink-expand kernel.

Three views:
  (a) modeled v5e latency + HBM utilization from the kernels' exact byte/flop
      traffic (the quantity Fig 19 plots; wall-clock needs a TPU)
  (b) measured CPU wall time of the jitted ref path (relative ordering
      sanity: SGMV's aggregation must beat BGMV's per-token gather when
      tokens-per-adapter is high)
  (c) the REAL Pallas kernels in interpret mode on tiny shapes (the body
      runs per grid step in Python — correctness-bearing wall time, not a
      perf number) with per-call host-dispatch counts: every Pallas kernel
      here is already one launch per call; the 2-launch baseline they all
      beat is the UNFUSED two-phase path (a shrink GEMM call, an HBM round
      trip of the intermediate, then an expand GEMM call — the cuBLAS-style
      batched-GEMM pair of Fig 19's generic baseline)
"""
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit
from repro.analysis.roofline import HBM_BW, PEAK_FLOPS
from repro.kernels import bgmv as bgmv_mod
from repro.kernels import fused as fused_mod
from repro.kernels import ops, ref
from repro.kernels import sgmv as sgmv_mod
from repro.serving.workload import zipf_popularity

RANK_MIX = (4, 8, 16, 64)       # mixed-rank pool buckets (zipf-weighted)


def zipf_rank_mix(n_adapters: int, seed: int = 0) -> np.ndarray:
    """Per-adapter TRUE ranks: zipf-weighted draw over ``RANK_MIX`` (small
    ranks dominate, the way fleets of task adapters actually look)."""
    rng = np.random.default_rng(seed)
    p = zipf_popularity(len(RANK_MIX), 1.2)
    return rng.choice(np.asarray(RANK_MIX), size=n_adapters, p=p)


def modeled_us(d, row_ranks, adapter_ranks):
    """Modeled v5e latency + HBM utilization of one hook phase from the
    kernels' exact byte/flop traffic, priced at each row's TRUE rank.
    Uniform pools pass constant ranks and recover the padded-pool model
    (the pre-rank-aware formula was this with every rank = pool rank).
    Shrink (d->r) and expand (r->d) move the same bytes/FLOPs, so one
    call prices either phase. Returns {kern: (us, hbm_util)} plus the
    total true-rank FLOPs under key "_flops"."""
    rr = np.asarray(row_ranks, float)
    ar = np.asarray(adapter_ranks, float)
    act = float(np.sum(d + rr)) * 2                  # read rows + write out
    w_bgmv = float(np.sum((d + rr) * rr)) * 2        # per-row gather
    w_sgmv = float(np.sum((d + ar) * ar)) * 2        # per-segment reuse
    flops = 2.0 * float(np.sum(rr * (d + rr)))
    t_flops = flops / (PEAK_FLOPS * 0.7)
    out = {"_flops": flops}
    for name, w in (("bgmv", w_bgmv), ("sgmv", w_sgmv)):
        t_mem = (act + w) / (HBM_BW * 0.7)
        out[name] = (max(t_mem, t_flops) * 1e6,
                     min((act + w) / max(t_mem, t_flops) / HBM_BW, 1.0))
    return out


def main():
    N, T, r, d = 512, 1024, 64, 4096
    rng = np.random.default_rng(0)
    probs = zipf_popularity(N, 1.2)
    ids = jnp.asarray(rng.choice(N, size=T, p=probs).astype(np.int32))
    distinct = len(set(np.asarray(ids).tolist()))

    # mixed-rank pool: the modeled rows price TRUE-rank FLOPs (the padded
    # model billed every row at the pool rank r regardless of its adapter)
    adapter_ranks = zipf_rank_mix(N, seed=0)
    row_ranks = adapter_ranks[np.asarray(ids)]
    distinct_ranks = adapter_ranks[sorted(set(np.asarray(ids).tolist()))]
    mean_rank = float(np.mean(row_ranks))

    for phase in ("shrink", "expand"):
        m = modeled_us(d, row_ranks, distinct_ranks)
        for kern in ("bgmv", "sgmv"):
            us, bw = m[kern]
            emit(f"fig19.{phase}.{kern}.modeled_us", round(us, 1),
                 f"hbm_util={bw:.2f},distinct={distinct},"
                 f"mean_rank={mean_rank:.1f}")
    true_flops = modeled_us(d, row_ranks, distinct_ranks)["_flops"]
    padded_flops = modeled_us(d, np.full(T, r), np.full(distinct, r)
                              )["_flops"]
    emit("fig19.rank.modeled_flop_reduction",
         round(padded_flops / true_flops, 2),
         f"padded r={r} vs zipf mix {RANK_MIX}, mean_rank={mean_rank:.1f}")

    for phase, d_in, d_out in (("shrink", d, r), ("expand", r, d)):
        # measured (CPU, jitted ref path — relative ordering only)
        key = jax.random.PRNGKey(0)
        x = jax.random.normal(key, (T, d_in), jnp.float32)
        A = jax.random.normal(jax.random.fold_in(key, 1), (N, d_in, r)) * .02
        B = jax.random.normal(jax.random.fold_in(key, 2), (N, r, d_out)) * .02
        # staticcheck: disable=SC003 (new shapes per phase; reused in loop)
        bg = jax.jit(lambda x, A, B, i: ref.bgmv_ref(x, A, B, i))
        bg(x, A, B, ids).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(3):
            bg(x, A, B, ids).block_until_ready()
        t_bgmv = (time.perf_counter() - t0) / 3 * 1e6

        segs, seg_ad, _ = ops.build_segments(x, ids, N, cap=64)
        # staticcheck: disable=SC003 (new shapes per phase; reused in loop)
        sg = jax.jit(lambda s, a, A, B: ref.sgmv_ref(s, a, A, B))
        sg(segs, seg_ad, A, B).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(3):
            sg(segs, seg_ad, A, B).block_until_ready()
        t_sgmv = (time.perf_counter() - t0) / 3 * 1e6
        emit(f"fig19.{phase}.bgmv.cpu_us", round(t_bgmv, 0))
        emit(f"fig19.{phase}.sgmv.cpu_us", round(t_sgmv, 0))

    pallas_interpret()


def _timed(fn, reps=2):
    fn().block_until_ready()
    t0 = time.perf_counter()
    for _ in range(reps):
        fn().block_until_ready()
    return (time.perf_counter() - t0) / reps * 1e6


def pallas_interpret():
    """The real Pallas kernels (interpret-safe on CPU: same blocking, body
    executed per grid step) on tiny shapes, vs their refs, with the
    host-dispatch count each path costs per hook invocation."""
    Np, T, r, d = 8, 16, 64, 128
    key = jax.random.PRNGKey(1)
    x = jax.random.normal(key, (T, d), jnp.float32)
    A = jax.random.normal(jax.random.fold_in(key, 1), (Np, d, r)) * .02
    B = jax.random.normal(jax.random.fold_in(key, 2), (Np, r, d)) * .02
    ids = jax.random.randint(jax.random.fold_in(key, 3), (T,), -1, Np)

    us = _timed(lambda: bgmv_mod.bgmv(x, A, B, ids, interpret=True))
    err = float(jnp.max(jnp.abs(bgmv_mod.bgmv(x, A, B, ids, interpret=True)
                                - ref.bgmv_ref(x, A, B, ids))))
    emit("fig19.pallas.bgmv.interpret_us", round(us, 0),
         f"max_err={err:.1e},dispatches_per_call=1 (per-token gather)")

    segs, seg_ad, _ = ops.build_segments(x, ids, Np, cap=8)
    us = _timed(lambda: sgmv_mod.sgmv(segs, seg_ad, A, B, interpret=True))
    err = float(jnp.max(jnp.abs(
        sgmv_mod.sgmv(segs, seg_ad, A, B, interpret=True)
        - ref.sgmv_ref(segs, seg_ad, A, B))))
    emit("fig19.pallas.sgmv.interpret_us", round(us, 0),
         f"max_err={err:.1e},dispatches_per_call=1")

    eids = jnp.zeros((segs.shape[0],), jnp.int32)
    us = _timed(lambda: fused_mod.fused_sgmv(segs, seg_ad, eids, A[:, None],
                                             B[:, None], interpret=True))
    err = float(jnp.max(jnp.abs(
        fused_mod.fused_sgmv(segs, seg_ad, eids, A[:, None], B[:, None],
                             interpret=True)
        - ref.fused_sgmv_ref(segs, seg_ad, eids, A[:, None], B[:, None]))))
    emit("fig19.pallas.fused.interpret_us", round(us, 0),
         f"max_err={err:.1e},dispatches_per_call=1 (A-then-B, VMEM "
         f"intermediate)")
    # per-decode-step hook dispatch budget the serving transports pay
    emit("fig19.dispatch.host_per_step", "2L+replicas",
         "per-hook host round trips (transport='host')")
    emit("fig19.dispatch.fused_per_step", 1,
         "one jitted program (transport='fused')")
    rank_interpret()


def rank_interpret():
    """Padded vs rank-aware SGMV on a zipf {4,8,16,64} mixed-rank pool:
    the rank-grouped dispatch slices each bucket's A/B to its true rank,
    so the interpret-mode K loop does true-rank work — a real wall-time
    win here, and the same output to f32 rounding (padded lanes are exact
    zeros; the sliced contraction sums in a different order)."""
    Np, T, r, d, cap = 8, 64, 64, 2048, 64
    key = jax.random.PRNGKey(2)
    x = jax.random.normal(key, (T, d), jnp.float32)
    A = np.asarray(jax.random.normal(jax.random.fold_in(key, 1),
                                     (Np, d, r))) * .02
    B = np.asarray(jax.random.normal(jax.random.fold_in(key, 2),
                                     (Np, r, d))) * .02
    ranks = zipf_rank_mix(Np, seed=3)
    for i, ra in enumerate(ranks):          # prefix-zeroed mixed-rank pool
        A[i, :, ra:] = 0.0
        B[i, ra:, :] = 0.0
    A, B = jnp.asarray(A), jnp.asarray(B)
    ids = jax.random.randint(jax.random.fold_in(key, 3), (T,), 0, Np)

    segs, seg_ad, _ = ops.build_segments(x, ids, Np, cap=cap)
    us_pad = _timed(lambda: sgmv_mod.sgmv(segs, seg_ad, A, B,
                                          interpret=True))
    seg_r, seg_a, seg_rank, _ = ops.build_segments_ranked(
        x, ids, Np, cap, ranks)
    env_old = os.environ.get("REPRO_USE_PALLAS")
    os.environ["REPRO_USE_PALLAS"] = "1"    # force the bucketed Pallas path
    try:
        us_rank = _timed(lambda: ops.sgmv_rank_grouped(seg_r, seg_a,
                                                       seg_rank, A, B))
        got = ops.sgmv_rank_grouped(seg_r, seg_a, seg_rank, A, B)
    finally:
        if env_old is None:
            os.environ.pop("REPRO_USE_PALLAS", None)
        else:
            os.environ["REPRO_USE_PALLAS"] = env_old
    want = sgmv_mod.sgmv(seg_r, seg_a, A, B, interpret=True)
    err = float(jnp.max(jnp.abs(got - want)))
    mean_rank = float(np.mean(ranks[np.asarray(ids)]))
    emit("fig19.rank.padded.interpret_us", round(us_pad, 0),
         f"pool r={r}, mix={sorted(set(int(x_) for x_ in ranks))}")
    emit("fig19.rank.grouped.interpret_us", round(us_rank, 0),
         f"mean_rank={mean_rank:.1f}, max_err={err:.1e}")
    emit("fig19.rank.interpret_speedup", round(us_pad / max(us_rank, 1e-9),
                                               2),
         "padded/grouped wall-time ratio, interpret mode")


if __name__ == "__main__":
    main()
