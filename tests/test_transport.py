"""GPU-initiated hook transport (paper §5 "GPU-initiated communication"):

  - slot-LUT correctness: ``LoRAServer.resolve_slots``' cached LUT is
    invalidated on every insert/evict and after ``ServerPool.resize_slots``
    re-homing (a stale LUT silently routes rows to the wrong adapter slot)
  - the acceptance claim: ``FusedTransport`` runs the whole disaggregated
    decode step as ONE jitted program — O(1) host dispatches per step vs
    O(L x replicas) on ``HostTransport`` — while token streams stay
    bit-identical across both transports, both KV layouts, 1 and 2 server
    replicas, adapter-cache eviction churn, and an autoscaler-driven
    resize mid-run
  - the hooks compute only the live (token, expert) pairs of the
    dispatch buffer: the same deltas bit for bit as over every row, on
    both the device view and the host server, with ``hook_rows`` rows
    per hook call and the ``hook_rows`` counter reading it
  - ``transport_stats()`` is exposed through ``ServeSystem`` on both
    execution planes, and the sim plane prices the host launch tail
    (``SimConfig.hook_launch_us``) that the fused plane avoids
"""
import dataclasses

import numpy as np
import pytest

from repro.configs import get_config
from repro.serving.autoscaler import AutoscalePolicy
from repro.serving.cache import LoRACache
from repro.serving.server_pool import ServerPool


# --------------------------- slot-LUT regressions ------------------------ #
def _mk_server(cfg, slots=4):
    import jax.numpy as jnp
    from repro.core.lora_server import LoRAServer, ServerConfig
    return LoRAServer(cfg, ServerConfig(m=1, x=1, y=1, cache_slots=slots,
                                        rank=4), dtype=jnp.float32)


@pytest.fixture(scope="module")
def model_cfg():
    return dataclasses.replace(get_config("qwen3-moe-235b-a22b").reduced(),
                               lora_targets=("gate", "up", "down"),
                               lora_rank=8)


def test_resolve_slots_lut_invalidated_on_insert_and_evict(model_cfg):
    """Satellite regression: the cached id->slot LUT must be rebuilt after
    EVERY insert and evict — reusing slot 0 for a different adapter with a
    stale LUT would route its rows to the evicted adapter's weights."""
    srv = _mk_server(model_cfg, slots=2)
    s7 = srv.insert(7)
    assert list(srv.resolve_slots([7, 3])) == [s7, -1]
    s3 = srv.insert(3)                       # insert AFTER a resolve
    assert list(srv.resolve_slots([7, 3])) == [s7, s3]
    srv.evict(7)
    assert list(srv.resolve_slots([7, 3])) == [-1, s3]
    s9 = srv.insert(9)                       # recycles adapter 7's slot
    assert s9 == s7
    assert list(srv.resolve_slots([9, 7, 3])) == [s9, -1, s3]
    # out-of-range and negative ids never index past the LUT
    assert list(srv.resolve_slots([-1, 10_000])) == [-1, -1]


def test_resolve_slots_lut_rehomed_after_pool_resize(model_cfg):
    """Satellite regression: ``ServerPool.resize_slots`` (and replica
    add/remove) force a FULL re-home sync, and every replica's resolve LUT
    reflects its post-re-home slot table — no stale foreign residents."""
    import jax.numpy as jnp
    from repro.core.adapter import init_adapter_pool
    import jax
    pool = init_adapter_pool(model_cfg, 6, jax.random.PRNGKey(0), rank=4,
                             dtype=jnp.float32)
    sp = ServerPool.build(model_cfg, pool, cache_slots=6, n_replicas=2)
    cache = LoRACache(6, adapter_bytes=0.0, n_layers=2, layerwise=False,
                      prefetch=False)
    for aid in (0, 1, 2, 3):
        cache.admit(aid, 0.0)
    sp.sync(cache)
    sp.check_consistent(cache)
    v0 = sp.version
    # replica 1 owns the odd adapters pre-resize
    assert list(sp.replicas[1].resolve_slots([1, 3])) != [-1, -1]
    sp.resize_slots(6)                      # must force a full re-home
    assert sp.version > v0 and sp._full_sync
    sp.sync(cache)
    sp.check_consistent(cache)
    # now scale in: replica 1's residents must re-home to replica 0 and
    # resolve there — and only there
    sp.remove_replica()
    sp.sync(cache)
    sp.check_consistent(cache)
    assert all(s >= 0 for s in sp.replicas[0].resolve_slots([0, 1, 2, 3]))


# ------------------- host == fused token equivalence --------------------- #
@pytest.fixture(scope="module")
def cluster_setup():
    import jax
    import jax.numpy as jnp
    from repro.core.adapter import init_mixed_rank_pool
    from repro.models import model as model_mod
    cfg = dataclasses.replace(get_config("qwen3-moe-235b-a22b").reduced(),
                              lora_targets=("gate", "up", "down"),
                              lora_rank=8)
    key = jax.random.PRNGKey(0)
    params = model_mod.init_params(cfg, key, dtype="float32")
    pool = init_mixed_rank_pool(cfg, [2, 8, 4, 8], jax.random.fold_in(key, 1),
                                dtype=jnp.float32)
    return cfg, params, pool


SPECS = [(0, 0.0, 5, 6), (1, 0.0, 4, 4), (2, 2.0, 6, 5), (3, 5.0, 3, 4)]


def _serve(setup, transport, *, paged=False, replicas=1, cache_slots=4,
           autoscale=None, rank_aware=True):
    from repro.serving.api import ServeConfig, build_system
    cfg, params, pool = setup
    sc = ServeConfig(backend="cluster", disaggregated=True, n_instances=1,
                     max_batch=2, max_len=32, adapter_cache_slots=cache_slots,
                     transport=transport, server_replicas=replicas,
                     paged=paged, page_size=4, n_pages=8, prefill_chunk=8,
                     autoscale=autoscale, rank_aware=rank_aware)
    system = build_system(sc, cfg, params=params, pool=pool)
    handles = [system.submit(adapter_id=a, arrival=t, prompt_len=p,
                             max_new_tokens=o) for a, t, p, o in SPECS]
    system.drain()
    assert all(h.state.name == "FINISHED" for h in handles)
    return {h.rid: h.tokens for h in handles}, system


@pytest.fixture(scope="module")
def host_tokens(cluster_setup):
    tokens, _ = _serve(cluster_setup, "host")
    return tokens


@pytest.mark.parametrize("paged,replicas",
                         [(False, 1), (True, 1), (False, 2), (True, 2)],
                         ids=["dense_1rep", "paged_1rep", "dense_2rep",
                              "paged_2rep"])
def test_fused_tokens_bit_identical_to_host(cluster_setup, host_tokens,
                                            paged, replicas):
    """Acceptance: the fused transport must not change a single token vs
    the host-mediated plane under continuous-batching churn, in either KV
    layout, with 1- and 2-replica server pools."""
    tokens, system = _serve(cluster_setup, "fused", paged=paged,
                            replicas=replicas)
    assert tokens == host_tokens
    st = system.transport_stats()
    assert st["transport"] == "fused"
    assert st["lut_uploads"] >= 1            # residency really uploaded


def test_fused_tokens_survive_eviction_churn(cluster_setup, host_tokens):
    """A 2-slot adapter cache forces evictions and slot reuse mid-run: the
    device LUT must be re-uploaded on every residency change (stale-LUT
    silent misrouting is exactly the failure this guards)."""
    h, hsys = _serve(cluster_setup, "host", cache_slots=2)
    f, fsys = _serve(cluster_setup, "fused", cache_slots=2)
    assert h == f == host_tokens
    cache = hsys.backend.cluster._caches[-1]
    assert cache.evictions > 0               # churn actually happened
    assert fsys.transport_stats()["lut_uploads"] > 2


def test_fused_tokens_invariant_under_autoscaler_resize(cluster_setup,
                                                        host_tokens):
    """An aggressive autoscaler (cache resizes + replica scale-out at
    2-round intervals, zero deadband) mid-run must leave the fused plane's
    tokens bit-identical — every re-home lands in the device LUT before
    the next decode step."""
    pol = AutoscalePolicy(control_interval=2.0, window=10.0,
                          min_instances=1, max_instances=3,
                          min_cache_slots=2, max_cache_slots=4,
                          max_replicas=2, scale_down_patience=1,
                          resize_deadband=0.0)
    tokens, system = _serve(cluster_setup, "fused", autoscale=pol)
    assert tokens == host_tokens
    assert system.scale_history()            # the control loop really ran


# ------------------------- dispatch accounting --------------------------- #
def test_fused_is_one_dispatch_per_step_host_is_2L(cluster_setup):
    """THE tentpole claim: host dispatches per decode step drop from
    O(L x replicas) to O(1). On the host plane every MoE layer makes two
    hook dispatches (plus gather/scatter/select); the fused plane launches
    exactly ONE program per step, with LUT uploads off the per-token
    path."""
    cfg, _, _ = cluster_setup
    L = cfg.n_layers
    _, hsys = _serve(cluster_setup, "host", replicas=2)
    _, fsys = _serve(cluster_setup, "fused", replicas=2)
    hs, fs = hsys.transport_stats(), fsys.transport_stats()
    assert hs["steps"] == fs["steps"] > 0
    # host: 2L hook calls/step, each >= 1 replica launch, + 3 overhead
    assert hs["hook_dispatches"] == 2 * L * hs["steps"]
    assert hs["host_dispatches"] >= (2 * L + 3) * hs["steps"]
    # fused: exactly one launch per step — O(1), not O(L)
    assert fs["host_dispatches"] == fs["steps"]
    assert fs["host_dispatches_per_step"] == 1.0
    assert fs["hook_dispatches"] == 0
    # uploads happen on residency changes, not per token
    assert 0 < fs["lut_uploads"] < fs["steps"]


def test_transport_stats_exposed_on_sim_plane():
    """`ServeSystem.transport_stats()` works on the analytic plane too
    (modeled counts with the same keys), and ``hook_launch_us`` prices the
    host launch tail the fused plane avoids: same workload, strictly worse
    TPOT under the host transport."""
    from repro.serving import workload
    from repro.serving.api import ServeConfig, build_system

    def run(transport):
        sc = ServeConfig(backend="sim", disaggregated=True, n_instances=2,
                         max_batch=8, duration=60.0, n_adapters=16,
                         adapter_cache_slots=8, transport=transport,
                         hook_launch_us=25.0)
        model = get_config("mixtral-8x7b")
        system = build_system(sc, model)
        reqs = workload.generate(n_adapters=16, rate=4.0, duration=40.0,
                                 seed=3)
        system.submit_workload(reqs)
        system.drain()
        return system

    host, fused = run("host"), run("fused")
    hs, fs = host.transport_stats(), fused.transport_stats()
    model = get_config("mixtral-8x7b")
    # modeled per-step host launches match the real plane's measured
    # ledger: 2L hook calls x 1 replica + gather/scatter/select
    assert hs["host_dispatches_per_step"] == 2 * model.n_layers + 3
    assert fs["host_dispatches_per_step"] == 1.0
    assert hs["steps"] > 0 and fs["steps"] > 0
    # the launch tail is real simulated time: host TPOT must be worse by
    # at least the per-step dispatch gap
    ht = host.summary().mean_tpot
    ft = fused.summary().mean_tpot
    assert ht > ft
    gap = (2 * model.n_layers + 3 - 1) * 25e-6
    assert ht - ft >= 0.5 * gap


def test_coupled_mode_has_no_transport(cluster_setup):
    """Coupled mode's step is one jit by construction — transport_stats is
    explicitly empty rather than fabricated."""
    from repro.serving.api import ServeConfig, build_system
    cfg, params, pool = cluster_setup
    sc = ServeConfig(backend="cluster", disaggregated=False, n_instances=1,
                     max_batch=2, max_len=32, adapter_cache_slots=4)
    system = build_system(sc, cfg, params=params, pool=pool)
    h = system.submit(adapter_id=0, prompt_len=4, max_new_tokens=2)
    system.drain()
    assert h.state.name == "FINISHED"
    assert system.transport_stats() == {}


def test_make_transport_rejects_unknown_plane():
    from repro.transport import make_transport
    with pytest.raises(ValueError, match="unknown transport"):
        make_transport("quantum", server=None)


def test_fused_transport_rejects_analytic_replicas():
    """The fused plane needs real slot pools to upload; the analytic
    plane's slot tables must be rejected loudly, not half-uploaded."""
    from repro.transport import FusedTransport
    sp = ServerPool.analytic(2, 4)
    tr = FusedTransport(sp, n_adapters=4)
    with pytest.raises(ValueError, match="analytic"):
        tr.refresh()


# ---------------------- rank-aware compute bit-identity ------------------- #
@pytest.mark.parametrize("transport", ["host", "fused"])
@pytest.mark.parametrize("paged,replicas",
                         [(False, 1), (True, 1), (False, 2), (True, 2)],
                         ids=["dense_1rep", "paged_1rep", "dense_2rep",
                              "paged_2rep"])
def test_rank_aware_off_tokens_bit_identical(cluster_setup, host_tokens,
                                             transport, paged, replicas):
    """Tentpole pin: bounding every hook at the slot's TRUE rank (the
    mixed-rank pool here is [2, 8, 4, 8], pool rank 8) must be
    bit-identical to padded compute. rank_aware=True is the default every
    other test in this module runs under, so pinning the rank_aware=False
    stream to the same tokens — with a 2-slot cache forcing eviction churn
    and slot reuse, on both planes, both KV layouts, 1 and 2 replicas —
    proves on == off across the whole matrix."""
    tokens, system = _serve(cluster_setup, transport, paged=paged,
                            replicas=replicas, cache_slots=2,
                            rank_aware=False)
    assert tokens == host_tokens
    st = system.transport_stats()
    # padded pricing: every active row bills the pool rank, zero savings
    assert st["mean_active_rank"] == st["max_active_rank"] == 8
    assert st["rank_flop_savings"] == 0.0


def test_rank_telemetry_prices_true_rank(cluster_setup):
    """On the mixed-rank pool [2, 8, 4, 8] (pool rank 8) the per-step
    ledger bills active rows at their true slot rank: mean strictly below
    the pool rank, max = the largest active rank, savings = 1 - mean/pool
    — on BOTH transports."""
    for transport in ("host", "fused"):
        _, system = _serve(cluster_setup, transport)
        st = system.transport_stats()
        assert 2 <= st["mean_active_rank"] < 8    # pool rank is 8
        assert st["max_active_rank"] == 8
        assert st["rank_flop_savings"] > 0
        assert abs(st["rank_flop_savings"]
                   - (1 - st["mean_active_rank"] / 8)) < 1e-3


# -------------------- device view numerics (unit level) ------------------ #
def test_device_view_matches_server_pool_compute(model_cfg):
    """Unit-level bit-compatibility: the fused plane's device-resident
    gather must reproduce ``ServerPool.compute``'s per-replica masked sum
    exactly (same f32 contraction per row, exact zeros elsewhere)."""
    import jax
    import jax.numpy as jnp
    from repro.core.adapter import init_adapter_pool
    from repro.core.lora_server import pool_tensors_from_adapter
    from repro.transport import FusedTransport, fused_hook_delta
    pool = init_adapter_pool(model_cfg, 4, jax.random.PRNGKey(1), rank=4,
                             dtype=jnp.float32)
    sp = ServerPool.build(model_cfg, pool, cache_slots=4, n_replicas=2)
    cache = LoRACache(4, adapter_bytes=0.0, n_layers=model_cfg.n_layers,
                      layerwise=False, prefetch=False)
    for aid in range(4):
        cache.admit(aid, 0.0)
    sp.sync(cache, tensors_fn=lambda a: pool_tensors_from_adapter(pool, a))
    tr = FusedTransport(sp, n_adapters=4)
    tr.refresh()
    rng = np.random.default_rng(0)
    E = max(model_cfg.n_experts, 1)
    rows = jnp.asarray(rng.normal(size=(8, model_cfg.d_model))
                       .astype(np.float32))
    ads = jnp.asarray(np.array([0, 1, 2, 3, -1, 0, 3, 1], np.int32))
    eids = jnp.asarray(rng.integers(0, E, 8).astype(np.int32))
    for layer in range(model_cfg.n_layers):
        want = sp.compute("up", layer, rows, np.asarray(ads),
                          np.asarray(eids))
        got = fused_hook_delta(tr._view, "up", layer, rows, ads, eids)
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))
    hrows = jnp.asarray(rng.normal(size=(8, model_cfg.d_ff))
                        .astype(np.float32))
    want = sp.compute("down", 0, hrows, np.asarray(ads), np.asarray(eids))
    got = fused_hook_delta(tr._view, "down", 0, hrows, ads, eids)
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


# ------------------ live-pair hooks (no padded dispatch rows) ------------- #
def _routing_cfg(E, K, capacity_factor=1.25):
    return dataclasses.replace(get_config("qwen3-moe-235b-a22b").reduced(),
                               n_experts=E, top_k=K, d_ff=64,
                               capacity_factor=capacity_factor,
                               lora_targets=("gate", "up", "down"),
                               lora_rank=8)


# name: (E, top_k, tokens, capacity or None = decode capacity, adapter
# ranks, inactive rows, one dynamic slice per block instead of a gather)
LIVE_PAIR_CASES = {
    "mixtral_routing": (8, 2, 8, None, [8, 8, 8, 8], 0, False),
    "qwen_routing": (16, 8, 4, None, [8, 8, 8, 8], 0, False),
    "inactive_slots": (8, 2, 8, None, [8, 8, 8, 8], 3, False),
    "mixed_ranks": (16, 8, 4, None, [2, 8, 4, 8], 0, False),
    "capacity_drops": (8, 2, 32, 4, [2, 8, 4, 8], 2, False),
    "per_row_slices": (8, 2, 8, None, [2, 8, 4, 8], 1, True),
}


@pytest.mark.parametrize("case", list(LIVE_PAIR_CASES))
def test_live_pair_hook_deltas_bit_identical_to_all_rows(case, monkeypatch):
    """The hooks compute only the live (token, expert) pairs and scatter
    their deltas back: the (E, C, d) deltas equal the all-rows computation
    over the whole dispatch buffer bit for bit, under the fused plane's
    device view and the host LoRAServer, for Mixtral- and Qwen-like
    routing, inactive rows (adapter -1), mixed true ranks and a capacity
    that drops pairs."""
    import jax
    import jax.numpy as jnp
    from repro.core import disagg
    from repro.core.adapter import init_mixed_rank_pool
    from repro.core.lora_server import pool_tensors_from_adapter
    from repro.models import moe as moe_mod
    from repro.transport import FusedTransport
    from repro.transport import fused as fused_mod
    E, K, T, C, ranks, n_off, per_row = LIVE_PAIR_CASES[case]
    if per_row:     # every block above the gather limit: the slice path
        monkeypatch.setattr(fused_mod, "_GATHER_SLICE_BYTES", 0)
    cfg = _routing_cfg(E, K)
    pool = init_mixed_rank_pool(cfg, ranks, jax.random.PRNGKey(E + K),
                                dtype=jnp.float32)
    sp = ServerPool.build(cfg, pool, cache_slots=4)
    cache = LoRACache(4, adapter_bytes=0.0, n_layers=cfg.n_layers,
                      layerwise=False, prefetch=False)
    for aid in range(4):
        cache.admit(aid, 0.0)
    sp.sync(cache, tensors_fn=lambda a: pool_tensors_from_adapter(pool, a),
            rank_fn=lambda a: ranks[a])
    tr = FusedTransport(sp, n_adapters=4)
    tr.refresh()

    rng = np.random.default_rng(T * E + K)
    C = C or disagg._capacity(cfg, T)
    ids = jnp.asarray(np.argsort(rng.normal(size=(T, E)), 1)[:, :K]
                      .astype(np.int32))
    x = jnp.asarray(rng.normal(size=(T, cfg.d_model)).astype(np.float32))
    xe, slot_tok = moe_mod.local_dispatch(x, ids, C, E)
    live = int(np.sum(np.asarray(slot_tok) < T))
    P = min(E * C, T * K)
    assert live <= P
    if case == "capacity_drops":
        assert live < T * K                  # pairs were dropped
    ads = rng.integers(0, 4, T).astype(np.int32)
    ads[:n_off] = -1
    tok = np.minimum(np.asarray(slot_tok), T - 1)
    row_adapter = jnp.asarray(np.where(np.asarray(slot_tok) < T, ads[tok],
                                       -1).astype(np.int32))
    row_expert = jnp.arange(E * C, dtype=jnp.int32) // C
    pairs = disagg._live_pairs(slot_tok, jnp.asarray(ads), T, P)
    # the down hook's rows are nonzero in dead slots too: their delta must
    # still come out exact 0.0
    h_rows = jnp.asarray(rng.normal(size=(E * C, cfg.d_ff))
                         .astype(np.float32))
    for server in (tr._view, sp.replicas[0]):
        for hook, rows in (("up", xe.reshape(E * C, -1)), ("down", h_rows)):
            for layer in range(cfg.n_layers):
                want = server.compute(hook, layer, rows, row_adapter,
                                      row_expert)
                got = disagg._hook_delta(server, hook, layer, rows, pairs, C)
                np.testing.assert_array_equal(
                    np.asarray(got).reshape(E, C, -1),
                    np.asarray(want).reshape(E, C, -1))


class _RecordingServer:
    """The ``compute`` contract, recording how many rows each call asks
    for; its deltas are zero."""

    def __init__(self, cfg):
        self.d_out = {"up": 2 * cfg.d_ff, "down": cfg.d_model}
        self.calls = []

    def compute(self, hook, layer, rows, adapter_ids, expert_ids):
        import jax.numpy as jnp
        assert len(adapter_ids) == len(expert_ids) == rows.shape[0]
        self.calls.append((hook, rows.shape[0]))
        return jnp.zeros((rows.shape[0], self.d_out[hook]), jnp.float32)


@pytest.mark.parametrize("E,K,T,cf", [(8, 2, 1, 1.25), (8, 2, 16, 1.25),
                                      (16, 8, 4, 1.25), (8, 2, 2049, 0.5)],
                         ids=["mixtral_T1", "mixtral_T16", "qwen_T4",
                              "not_dropless"])
def test_hooks_ask_server_for_live_pair_rows_only(E, K, T, cf):
    """Each hook call asks the server for min(E*C, T*top_k) rows, not the
    E*C rows of the dispatch buffer. Dropless decode gives T*top_k; a
    capacity below T*top_k / E (not dropless) gives E*C."""
    import jax
    import jax.numpy as jnp
    from repro.core import disagg
    from repro.models import model as model_mod
    cfg = _routing_cfg(E, K, capacity_factor=cf)
    params = model_mod.init_params(cfg, jax.random.PRNGKey(0),
                                   dtype="float32")
    x = jax.random.normal(jax.random.PRNGKey(1), (T, 1, cfg.d_model))
    rec = _RecordingServer(cfg)
    disagg._moe_hooks_layer(x, disagg._layer_params(params, 0), cfg, 0, rec,
                            jnp.zeros(T, jnp.int32), 1.0)
    C = disagg._capacity(cfg, T)
    want = min(E * C, T * K)
    assert want == disagg.hook_rows(cfg, T)
    assert want == (E * C if cf < 1 else T * K) and want <= E * C
    assert rec.calls == [("up", want), ("down", want)]


def test_hook_rows_counter_reads_rows_per_hook_call(cluster_setup):
    """With tracing on, each disaggregated engine step samples hook_rows
    beside decode_bucket: the rows one hook call computes at that bucket,
    min(E*C, bucket*top_k)."""
    from repro.core import disagg
    from repro.serving.api import ServeConfig, build_system
    cfg, params, pool = cluster_setup
    sc = ServeConfig(backend="cluster", disaggregated=True, n_instances=1,
                     max_batch=4, max_len=32, adapter_cache_slots=4,
                     transport="fused", paged=True, page_size=4, n_pages=16,
                     prefill_chunk=8, trace=True)
    system = build_system(sc, cfg, params=params, pool=pool)
    for a, t, p, o in SPECS:
        system.submit(adapter_id=a, arrival=t, prompt_len=p,
                      max_new_tokens=o)
    system.drain()
    tr = system.observability().tracer
    buckets = [(t, v) for k, n, t, v in tr.counters
               if (k, n) == ("engine", "decode_bucket")]
    rows = [(t, v) for k, n, t, v in tr.counters
            if (k, n) == ("engine", "hook_rows")]
    assert buckets and [t for t, _ in rows] == [t for t, _ in buckets]
    assert {int(b) for _, b in buckets} >= {1, 2}
    for (_, b), (_, n) in zip(buckets, rows):
        assert n == disagg.hook_rows(cfg, int(b)) == int(b) * cfg.top_k
    system.close()
