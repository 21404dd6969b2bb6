"""Per-kernel allclose vs the pure-jnp oracle, swept over shapes and dtypes
(Pallas interpret mode executes the kernel body on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


@pytest.fixture(autouse=True)
def _force_pallas(monkeypatch):
    """Force the kernel path in this module only (no env leak)."""
    monkeypatch.setenv("REPRO_USE_PALLAS", "1")

SHAPES = [  # (T, d_in, r, d_out, N)
    (4, 64, 16, 64, 3),
    (16, 128, 64, 256, 5),
    (33, 384, 32, 128, 9),   # non-aligned T and padded dims
    (8, 896, 64, 1536, 2),
]
DTYPES = [jnp.float32, jnp.bfloat16]


def _tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 else \
        dict(atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_bgmv(shape, dtype):
    T, d_in, r, d_out, N = shape
    key = jax.random.PRNGKey(hash(shape) % 2**31)
    x = jax.random.normal(key, (T, d_in), dtype)
    A = (jax.random.normal(jax.random.fold_in(key, 1), (N, d_in, r)) *
         0.05).astype(dtype)
    B = (jax.random.normal(jax.random.fold_in(key, 2), (N, r, d_out)) *
         0.05).astype(dtype)
    ids = jax.random.randint(jax.random.fold_in(key, 3), (T,), -1, N)
    got = ops.bgmv(x, A, B, ids)
    want = ref.bgmv_ref(x, A, B, ids)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               **_tol(dtype))
    # masked rows are exactly zero
    assert np.all(np.asarray(got)[np.asarray(ids) < 0] == 0)


@pytest.mark.parametrize("shape", SHAPES[:3])
@pytest.mark.parametrize("E", [2, 5])
def test_bgmv_expert(shape, E):
    T, d_in, r, d_out, N = shape
    key = jax.random.PRNGKey(E)
    x = jax.random.normal(key, (T, d_in))
    A = jax.random.normal(jax.random.fold_in(key, 1), (N, E, d_in, r)) * 0.05
    B = jax.random.normal(jax.random.fold_in(key, 2), (N, E, r, d_out)) * 0.05
    ids = jax.random.randint(jax.random.fold_in(key, 3), (T,), -1, N)
    eids = jax.random.randint(jax.random.fold_in(key, 4), (T,), 0, E)
    got = ops.bgmv_expert(x, A, B, ids, eids)
    want = ref.bgmv_expert_ref(x, A, B, ids, eids)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("cap", [4, 8, 16])
def test_sgmv_and_segments(cap):
    T, d_in, r, d_out, N = 37, 128, 16, 64, 6
    key = jax.random.PRNGKey(cap)
    x = jax.random.normal(key, (T, d_in))
    A = jax.random.normal(jax.random.fold_in(key, 1), (N, d_in, r)) * 0.05
    B = jax.random.normal(jax.random.fold_in(key, 2), (N, r, d_out)) * 0.05
    row_ad = jax.random.randint(jax.random.fold_in(key, 3), (T,), 0, N)
    segs, seg_ad, scatter = ops.build_segments(x, row_ad, N, cap)
    got = ops.sgmv(segs, seg_ad, A, B)
    want = ref.sgmv_ref(segs, seg_ad, A, B)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    # segment layout: every kept row's slot maps back to its adapter
    segs_np, slot = np.asarray(segs), np.asarray(scatter)
    kept = slot < N * cap
    rows = np.asarray(x)
    for i in np.nonzero(kept)[0][:10]:
        a = slot[i] // cap
        assert a == int(np.asarray(row_ad)[i])
        np.testing.assert_allclose(segs_np.reshape(-1, d_in)[slot[i]],
                                   rows[i], atol=1e-6)


@pytest.mark.parametrize("shape",  # (S, cap, d_in, r, d_out, M, E)
                         [(5, 8, 128, 128, 256, 3, 2),
                          (7, 6, 100, 60, 200, 4, 3),    # padded dims
                          (3, 16, 256, 32, 128, 2, 1)])
@pytest.mark.parametrize("dtype", DTYPES)
def test_fused_sgmv(shape, dtype):
    """Fused shrink-expand server-hook kernel: grouped A-then-B in ONE
    pallas_call with the (cap, r) intermediate in VMEM scratch — must match
    the composed-einsum oracle, incl. padding segments (slot -1) and
    tile-unaligned dims through the ops wrapper."""
    S, cap, d_in, r, d_out, M, E = shape
    key = jax.random.PRNGKey(S * 100 + cap)
    x = jax.random.normal(key, (S, cap, d_in), jnp.float32).astype(dtype)
    A = (jax.random.normal(jax.random.fold_in(key, 1), (M, E, d_in, r))
         * 0.05).astype(dtype)
    B = (jax.random.normal(jax.random.fold_in(key, 2), (M, E, r, d_out))
         * 0.05).astype(dtype)
    slots = jax.random.randint(jax.random.fold_in(key, 3), (S,), -1, M)
    eids = jax.random.randint(jax.random.fold_in(key, 4), (S,), 0, E)
    got = ops.fused_sgmv(x, slots, eids, A, B)
    want = ref.fused_sgmv_ref(x, slots, eids, A, B)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               **_tol(dtype))
    # padding segments are exact zeros, not small numbers
    got_np = np.asarray(got)
    for s in np.nonzero(np.asarray(slots) < 0)[0]:
        assert np.all(got_np[s] == 0.0)


def test_fused_sgmv_matches_two_phase_sgmv():
    """The fused kernel computes exactly what shrink-then-expand computes —
    collapsing two launches (and an HBM round trip of the intermediate)
    into one, not changing the math."""
    S, cap, d_in, r, d_out, M = 4, 8, 128, 64, 128, 3
    key = jax.random.PRNGKey(9)
    x = jax.random.normal(key, (S, cap, d_in))
    A = jax.random.normal(jax.random.fold_in(key, 1), (M, d_in, r)) * 0.05
    B = jax.random.normal(jax.random.fold_in(key, 2), (M, r, d_out)) * 0.05
    slots = jnp.asarray([0, -1, 2, 1], jnp.int32)
    eids = jnp.zeros((S,), jnp.int32)
    fused = ops.fused_sgmv(x, slots, eids, A[:, None], B[:, None])
    two_phase = ref.sgmv_ref(x, slots, A, B)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(two_phase),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("E,C,d,f", [(4, 12, 64, 96), (8, 8, 256, 512),
                                     (3, 16, 384, 640)])
def test_gmm(E, C, d, f):
    key = jax.random.PRNGKey(E * 1000 + C)
    xe = jax.random.normal(key, (E, C, d))
    w = jax.random.normal(jax.random.fold_in(key, 1), (E, d, f)) * 0.05
    gs = jax.random.randint(jax.random.fold_in(key, 2), (E,), 0, C + 1)
    got = ops.gmm(xe, w, gs)
    want = ref.gmm_ref(xe, w, gs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)
    # rows past a group's size are zeroed (skip-empty-tiles semantics)
    got_np = np.asarray(got)
    for e in range(E):
        assert np.all(got_np[e, int(gs[e]):] == 0)


def test_build_segments_padding_rows_do_not_shift_adapter0():
    """Regression: padding rows (adapter -1) were counted into adapter 0's
    bincount, so adapter 0's segment positions started at n_padding and a
    FULL adapter-0 segment silently dropped rows once count0 > cap - n_pad."""
    T, d, N, cap = 10, 8, 3, 4
    key = jax.random.PRNGKey(0)
    rows = jax.random.normal(key, (T, d))
    # 3 padding rows + adapter 0 filled EXACTLY to capacity
    row_ad = jnp.asarray([-1, -1, -1, 0, 0, 0, 0, 1, 2, 2])
    segs, seg_ad, scatter = ops.build_segments(rows, row_ad, N, cap)
    slot = np.asarray(scatter)
    kept = slot < N * cap
    # every real row must be kept (no adapter exceeds cap)
    assert kept.sum() == 7
    assert np.all(~kept[np.asarray(row_ad) < 0])
    segs_np = np.asarray(segs).reshape(-1, d)
    for i in np.nonzero(kept)[0]:
        assert slot[i] // cap == int(row_ad[i])
        np.testing.assert_allclose(segs_np[slot[i]], np.asarray(rows)[i],
                                   atol=1e-6)
    # adapter-0 rows occupy positions 0..3 of segment 0, not n_pad..cap-1
    assert sorted(slot[np.asarray(row_ad) == 0] % cap) == [0, 1, 2, 3]
    # sgmv over the segments matches the oracle
    A = jax.random.normal(jax.random.fold_in(key, 1), (N, d, 16)) * 0.05
    B = jax.random.normal(jax.random.fold_in(key, 2), (N, 16, 32)) * 0.05
    np.testing.assert_allclose(np.asarray(ops.sgmv(segs, seg_ad, A, B)),
                               np.asarray(ref.sgmv_ref(segs, seg_ad, A, B)),
                               atol=2e-5)


def test_build_segments_all_padding_marks_empty_adapters():
    rows = jnp.ones((4, 8))
    row_ad = jnp.asarray([-1, -1, -1, -1])
    _, seg_ad, scatter = ops.build_segments(rows, row_ad, 3, 4)
    assert np.all(np.asarray(seg_ad) == -1)
    assert np.all(np.asarray(scatter) == 3 * 4)


# --------------------------- rank-aware kernels --------------------------- #
# The mixed-rank invariant: pools prefix-zero every lane >= the adapter's
# true rank, so bounding the compute at the true rank trims only exact-zero
# work. Where the rank-aware variant and its padded twin are the same
# program with a mask added, they are BIT-identical (assert_array_equal).
# Where they are different programs (a Pallas kernel against an XLA einsum,
# or a rank-sliced contraction against the padded one), the f32 sums run in
# a different order and agree only to f32 rounding: _F32_REORDER.

# Reassociating a length-n f32 dot changes it by at most ~n * 2^-24 of the
# sum of |terms|; at n <= 128 that is < 1e-5 relative. atol covers outputs
# that cancel to near zero.
_F32_REORDER = dict(rtol=1e-5, atol=1e-6)

def _mixed_rank_pool(key, N, d_in, r, d_out, ranks):
    A = np.asarray(jax.random.normal(jax.random.fold_in(key, 1),
                                     (N, d_in, r))) * 0.05
    B = np.asarray(jax.random.normal(jax.random.fold_in(key, 2),
                                     (N, r, d_out))) * 0.05
    for i, ra in enumerate(ranks):
        A[i, :, ra:] = 0.0
        B[i, ra:, :] = 0.0
    return jnp.asarray(A), jnp.asarray(B)


def test_bgmv_ranked_bitwise_vs_padded():
    """bgmv_ranked masks the accumulator at each row's TRUE rank; on a
    prefix-zeroed pool that is bit-identical to the padded bgmv kernel, and
    bgmv_ranked_ref is bit-identical to bgmv_ref. Kernel against oracle is
    two programs, so they agree to f32 reordering (incl. masked rows,
    ids < 0, which are exact zeros in both)."""
    T, d, r, N = 24, 128, 32, 5
    ranks = np.asarray([4, 8, 16, 32, 8], np.int32)
    key = jax.random.PRNGKey(11)
    x = jax.random.normal(key, (T, d))
    A, B = _mixed_rank_pool(key, N, d, r, 64, ranks)
    ids = jax.random.randint(jax.random.fold_in(key, 3), (T,), -1, N)
    got = ops.bgmv_ranked(x, A, B, ids, jnp.asarray(ranks))
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(ops.bgmv(x, A, B, ids)))
    want = ref.bgmv_ranked_ref(x, A, B, ids, jnp.asarray(ranks))
    np.testing.assert_array_equal(np.asarray(want),
                                  np.asarray(ref.bgmv_ref(x, A, B, ids)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               **_F32_REORDER)
    assert np.all(np.asarray(got)[np.asarray(ids) < 0] == 0.0)


def test_sgmv_ranked_bitwise_vs_padded():
    """sgmv_ranked over build_segments_ranked output == padded sgmv over
    the same (rank-sorted) segments, bitwise, and == sgmv_ranked_ref."""
    T, d, r, d_out, N, cap = 37, 128, 32, 64, 6, 8
    ranks = np.asarray([4, 8, 16, 32, 8, 4], np.int32)
    key = jax.random.PRNGKey(12)
    x = jax.random.normal(key, (T, d))
    A, B = _mixed_rank_pool(key, N, d, r, d_out, ranks)
    row_ad = jax.random.randint(jax.random.fold_in(key, 3), (T,), 0, N)
    segs, seg_ad, seg_rank, _ = ops.build_segments_ranked(
        x, row_ad, N, cap, ranks)
    got = ops.sgmv_ranked(segs, seg_ad, seg_rank, A, B)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(ops.sgmv(segs, seg_ad, A, B)))
    np.testing.assert_array_equal(
        np.asarray(got),
        np.asarray(ref.sgmv_ranked_ref(segs, seg_ad, seg_rank, A, B)))


def test_sgmv_rank_grouped_bitwise_vs_padded():
    """The rank-bucketed dispatch (one launch per distinct rank, A/B sliced
    to the bucket rank) changes the work, not the math: its oracle
    sgmv_rank_grouped_ref is bit-identical to sgmv_ref. The bucketed kernel
    contracts over the sliced rank, a different f32 summation than the
    padded one, so it agrees with padded sgmv and with its oracle to f32
    reordering."""
    T, d, r, d_out, N, cap = 53, 128, 64, 64, 6, 8
    ranks = np.asarray([4, 8, 16, 64, 8, 4], np.int32)
    key = jax.random.PRNGKey(13)
    x = jax.random.normal(key, (T, d))
    A, B = _mixed_rank_pool(key, N, d, r, d_out, ranks)
    row_ad = jax.random.randint(jax.random.fold_in(key, 3), (T,), 0, N)
    segs, seg_ad, seg_rank, _ = ops.build_segments_ranked(
        x, row_ad, N, cap, ranks)
    got = ops.sgmv_rank_grouped(segs, seg_ad, seg_rank, A, B)
    want = ref.sgmv_rank_grouped_ref(segs, seg_ad, seg_rank, A, B)
    np.testing.assert_array_equal(
        np.asarray(want), np.asarray(ref.sgmv_ref(segs, seg_ad, A, B)))
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ops.sgmv(segs, seg_ad, A, B)),
                               **_F32_REORDER)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               **_F32_REORDER)


def test_fused_sgmv_ranked_bitwise_vs_padded():
    """fused_sgmv_ranked (per-segment rank masks the VMEM intermediate)
    == padded fused_sgmv bitwise on a prefix-zeroed (slot, expert) pool,
    and == fused_sgmv_ranked_ref; padding segments stay exact zeros."""
    S, cap, d, r, d_out, M, E = 5, 8, 128, 32, 64, 3, 2
    ranks = np.asarray([4, 16, 8], np.int32)
    key = jax.random.PRNGKey(14)
    x = jax.random.normal(key, (S, cap, d))
    A = np.asarray(jax.random.normal(jax.random.fold_in(key, 1),
                                     (M, E, d, r))) * 0.05
    B = np.asarray(jax.random.normal(jax.random.fold_in(key, 2),
                                     (M, E, r, d_out))) * 0.05
    for m, ra in enumerate(ranks):
        A[m, :, :, ra:] = 0.0
        B[m, :, ra:, :] = 0.0
    A, B = jnp.asarray(A), jnp.asarray(B)
    slots = jnp.asarray([0, -1, 2, 1, 0], jnp.int32)
    eids = jnp.asarray([0, 0, 1, 1, 0], jnp.int32)
    seg_rank = jnp.where(slots >= 0,
                         jnp.asarray(ranks)[jnp.maximum(slots, 0)], 0)
    got = ops.fused_sgmv_ranked(x, slots, eids, seg_rank, A, B)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(ops.fused_sgmv(x, slots, eids, A, B)))
    np.testing.assert_array_equal(
        np.asarray(got),
        np.asarray(ref.fused_sgmv_ranked_ref(x, slots, eids, seg_rank,
                                             A, B)))
    assert np.all(np.asarray(got)[1] == 0.0)


def test_build_segments_ranked_bucket_partition_and_roundtrip():
    """build_segments_ranked: active segments form a contiguous prefix in
    ascending-rank order (each rank bucket is one contiguous slice),
    seg_rank carries the adapter's true rank, and the remapped scatter
    still round-trips every kept row to its input row."""
    T, d, N, cap = 41, 16, 7, 8
    ranks = np.asarray([8, 4, 16, 4, 32, 8, 4], np.int32)
    key = jax.random.PRNGKey(5)
    rows = jax.random.normal(key, (T, d))
    row_ad = jax.random.randint(jax.random.fold_in(key, 1), (T,), -1, N)
    segs, seg_ad, seg_rank, scatter = ops.build_segments_ranked(
        rows, row_ad, N, cap, ranks)
    seg_ad_np, seg_rank_np = np.asarray(seg_ad), np.asarray(seg_rank)
    active = seg_ad_np >= 0
    assert np.all(np.nonzero(active)[0] == np.arange(active.sum()))
    assert np.all(np.diff(seg_rank_np[active]) >= 0)
    np.testing.assert_array_equal(seg_rank_np[active],
                                  ranks[seg_ad_np[active]])
    assert np.all(seg_rank_np[~active] == 0)
    slot = np.asarray(scatter)
    kept = slot < N * cap
    assert np.all(~kept[np.asarray(row_ad) < 0])
    segs_np = np.asarray(segs).reshape(-1, d)
    for i in np.nonzero(kept)[0]:
        assert seg_ad_np[slot[i] // cap] == int(np.asarray(row_ad)[i])
        np.testing.assert_allclose(segs_np[slot[i]], np.asarray(rows)[i],
                                   atol=1e-6)


def test_build_segments_ranked_padding_rows_do_not_shift_adapter0():
    """The adapter-0 padding regression (padding rows miscounted into
    adapter 0's bincount) must stay fixed through the rank permutation."""
    T, d, N, cap = 10, 8, 3, 4
    key = jax.random.PRNGKey(0)
    rows = jax.random.normal(key, (T, d))
    row_ad = jnp.asarray([-1, -1, -1, 0, 0, 0, 0, 1, 2, 2])
    ranks = np.asarray([16, 4, 8], np.int32)   # adapter 0 sorts LAST
    segs, seg_ad, _, scatter = ops.build_segments_ranked(
        rows, row_ad, N, cap, ranks)
    slot = np.asarray(scatter)
    kept = slot < N * cap
    assert kept.sum() == 7                     # no adapter-0 row dropped
    assert np.all(~kept[np.asarray(row_ad) < 0])
    segs_np = np.asarray(segs).reshape(-1, d)
    for i in np.nonzero(kept)[0]:
        assert int(np.asarray(seg_ad)[slot[i] // cap]) == int(row_ad[i])
        np.testing.assert_allclose(segs_np[slot[i]], np.asarray(rows)[i],
                                   atol=1e-6)
    # adapter-0 rows fill positions 0..3 of ONE segment, wherever rank
    # sorting moved it
    mask0 = np.asarray(row_ad) == 0
    assert sorted(slot[mask0] % cap) == [0, 1, 2, 3]
    assert len(set(slot[mask0] // cap)) == 1


def test_ranked_ref_path_dispatch(monkeypatch):
    """Rank-aware ops fall back to their _ref twins when kernels are off."""
    monkeypatch.setenv("REPRO_USE_PALLAS", "0")
    T, d, r, N, cap = 12, 64, 16, 3, 4
    ranks = np.asarray([4, 16, 8], np.int32)
    key = jax.random.PRNGKey(6)
    x = jax.random.normal(key, (T, d))
    A, B = _mixed_rank_pool(key, N, d, r, 32, ranks)
    ids = jax.random.randint(jax.random.fold_in(key, 3), (T,), -1, N)
    np.testing.assert_array_equal(
        np.asarray(ops.bgmv_ranked(x, A, B, ids, jnp.asarray(ranks))),
        np.asarray(ref.bgmv_ranked_ref(x, A, B, ids, jnp.asarray(ranks))))
    segs, seg_ad, seg_rank, _ = ops.build_segments_ranked(
        x, jnp.maximum(ids, 0), N, cap, ranks)
    np.testing.assert_array_equal(
        np.asarray(ops.sgmv_rank_grouped(segs, seg_ad, seg_rank, A, B)),
        np.asarray(ref.sgmv_rank_grouped_ref(segs, seg_ad, seg_rank, A, B)))


# --------------------------- paged attention ----------------------------- #
PAGED_SHAPES = [  # (B, KV, G, hd, P, page_size, nb)
    (4, 2, 3, 16, 10, 4, 5),
    (3, 1, 4, 64, 6, 8, 3),
    (2, 4, 2, 32, 16, 2, 8),
]


def _paged_case(shape, seed=0, window=0):
    B, KV, G, hd, P, ps, nb = shape
    key = jax.random.PRNGKey(seed)
    q = jax.random.normal(key, (B, KV, G, hd))
    kp = jax.random.normal(jax.random.fold_in(key, 1), (P, ps, KV, hd))
    vp = jax.random.normal(jax.random.fold_in(key, 2), (P, ps, KV, hd))
    # per-row position (-1 = inactive) and a block table allocating exactly
    # the pages that cover it, from a random non-overlapping page permutation
    rng = np.random.default_rng(seed)
    pos = rng.integers(-1, nb * ps, B).astype(np.int32)
    pos[0] = -1  # always exercise an inactive row
    perm = rng.permutation(P)
    bt = np.full((B, nb), -1, np.int32)
    take = 0
    for b in range(B):
        for j in range((pos[b] + ps) // ps if pos[b] >= 0 else 0):
            bt[b, j] = perm[take % P]
            take += 1
    return q, kp, vp, jnp.asarray(bt), jnp.asarray(pos)


def _paged_oracle(q, kp, vp, bt, pos, window=0):
    """Straight-line numpy oracle: materialize each row's keys and run a
    full softmax (independent of both the kernel and ref.py)."""
    q, kp, vp = map(np.asarray, (q, kp, vp))
    bt, pos = np.asarray(bt), np.asarray(pos)
    B, KV, G, hd = q.shape
    ps = kp.shape[1]
    out = np.zeros((B, KV, G, hd), np.float32)
    for b in range(B):
        if pos[b] < 0:
            continue
        n = pos[b] + 1
        pages = bt[b, : (n + ps - 1) // ps]
        k = kp[pages].reshape(-1, KV, hd)[:n]
        v = vp[pages].reshape(-1, KV, hd)[:n]
        lo = max(0, n - window) if window else 0
        k, v = k[lo:], v[lo:]
        s = np.einsum("kgd,skd->kgs", q[b], k) / np.sqrt(hd)
        e = np.exp(s - s.max(-1, keepdims=True))
        out[b] = np.einsum("kgs,skd->kgd", e / e.sum(-1, keepdims=True), v)
    return out


@pytest.mark.parametrize("shape", PAGED_SHAPES)
def test_paged_attention_kernel_vs_ref(shape):
    q, kp, vp, bt, pos = _paged_case(shape)
    got = ops.paged_attention(q, kp, vp, bt, pos)
    want = ref.paged_attention_ref(q, kp, vp, bt, pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(got),
                               _paged_oracle(q, kp, vp, bt, pos),
                               atol=1e-5, rtol=1e-5)
    # inactive rows are exactly zero, never NaN
    assert np.all(np.asarray(got)[np.asarray(pos) < 0] == 0)
    assert np.all(np.isfinite(np.asarray(got)))


@pytest.mark.parametrize("window", [3, 8])
def test_paged_attention_sliding_window(window):
    shape = PAGED_SHAPES[0]
    q, kp, vp, bt, pos = _paged_case(shape, seed=window)
    got = ops.paged_attention(q, kp, vp, bt, pos, window=window)
    want = ref.paged_attention_ref(q, kp, vp, bt, pos, window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(got), _paged_oracle(q, kp, vp, bt, pos, window),
        atol=1e-5, rtol=1e-5)


def test_paged_attention_matches_contiguous_decode():
    """Scattering a contiguous cache into randomly-permuted pages must not
    change attention: paged(q, pool, bt) == dense flash-decode over the
    original (B, S, KV, hd) cache."""
    B, KV, G, hd, ps, nb = 3, 2, 2, 32, 4, 4
    S = ps * nb
    key = jax.random.PRNGKey(7)
    q = jax.random.normal(key, (B, KV, G, hd))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, KV, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, KV, hd))
    pos = jnp.asarray([S - 1, 5, 9])
    rng = np.random.default_rng(7)
    perm = rng.permutation(B * nb)
    P = B * nb
    kp = np.zeros((P, ps, KV, hd), np.float32)
    vp = np.zeros((P, ps, KV, hd), np.float32)
    bt = np.zeros((B, nb), np.int32)
    for b in range(B):
        for j in range(nb):
            pg = perm[b * nb + j]
            bt[b, j] = pg
            kp[pg] = np.asarray(k)[b, j * ps:(j + 1) * ps]
            vp[pg] = np.asarray(v)[b, j * ps:(j + 1) * ps]
    got = ops.paged_attention(q, jnp.asarray(kp), jnp.asarray(vp),
                              jnp.asarray(bt), pos)
    # dense flash-decode reference (same masking semantics)
    from repro.models import layers as ll
    m, l, o = ll._local_decode_scores(
        q, k, v, jnp.arange(S, dtype=jnp.int32), pos + 1, 0)
    want = np.asarray(o / jnp.maximum(l, 1e-20)[..., None])
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-5, rtol=1e-5)


def test_ref_path_dispatch(monkeypatch):
    """ops falls back to the jnp oracle when kernels are disabled."""
    monkeypatch.setenv("REPRO_USE_PALLAS", "0")
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (4, 64))
    A = jax.random.normal(jax.random.fold_in(key, 1), (2, 64, 16))
    B = jax.random.normal(jax.random.fold_in(key, 2), (2, 16, 32))
    ids = jnp.array([0, 1, -1, 0])
    np.testing.assert_allclose(np.asarray(ops.bgmv(x, A, B, ids)),
                               np.asarray(ref.bgmv_ref(x, A, B, ids)),
                               atol=1e-6)
