"""Tier-1 tests for the observability plane (src/repro/obs).

Pins the PR-10 contracts:
  - trace schema: spans have start <= end, stage spans on a request
    track are contiguous and ordered queued -> prefill -> decode, and
    the sim plane's virtual-time record is monotone
  - tracing is bitwise invisible: token streams (cluster) and event
    streams (sim) are identical with trace on vs off, dense+host AND
    paged+fused
  - trace=True covers each request's full TTFT window (>= 95%: queue
    wait + staging/prefill attribution)
  - NullTracer is the zero-cost default: enabled=False and the no-op
    fast path (scope included) allocates nothing
  - exporters match golden files (tests/golden/obs_*)
  - the cluster plane's serve.* scopes are on the wall clock, nest, and
    hold one serve.engine.step per busy instance a round; with tracing
    off no scope arguments are built; the compiled decode step names its
    regions (lora_hook / moe_experts / attention)
"""
import dataclasses
import json
import pathlib
import re
import tracemalloc

import pytest

from repro.configs import get_config
from repro.obs import (NULL_TRACER, MetricsRegistry, NullTracer,
                       TimelineTracer, to_jsonl, to_perfetto, to_prometheus,
                       wall_time)
from repro.obs.trace import NO_SCOPE
from repro.serving.api import ServeConfig, build_system

GOLDEN = pathlib.Path(__file__).resolve().parent / "golden"


# ----------------------------- tracer unit ------------------------------ #
def test_null_tracer_is_the_default_and_disabled():
    assert isinstance(NULL_TRACER, NullTracer)
    assert NULL_TRACER.enabled is False
    # the front door wires it when trace=False
    sys_off = build_system(
        ServeConfig(backend="sim", duration=5.0), get_config(
            "qwen3-moe-235b-a22b").reduced())
    assert sys_off.tracer is NULL_TRACER
    assert sys_off.observability().tracer is NULL_TRACER


def test_null_tracer_fast_path_allocates_nothing():
    tr = NULL_TRACER
    # warm up method binding before the measured window
    tr.begin("a", "b", 0.0)
    tr.end("a", "b", 1.0)
    tracemalloc.start()
    snap1 = tracemalloc.take_snapshot()
    for _ in range(1000):
        tr.begin("a", "b", 0.0)
        tr.end("a", "b", 1.0)
        tr.instant("a", "c", 0.5)
        tr.counter("a", "d", 0.5, 1.0)
        tr.span("a", "e", 0.0, 1.0)
    snap2 = tracemalloc.take_snapshot()
    tracemalloc.stop()
    import repro.obs.trace as trace_mod
    grew = [s for s in snap2.compare_to(snap1, "lineno")
            if s.size_diff > 0
            and s.traceback[0].filename == trace_mod.__file__]
    assert not grew, grew


def test_null_tracer_scope_allocates_nothing():
    tr = NULL_TRACER
    assert tr.scope("serve.a") is tr.scope("serve.b", rows=2)
    with tr.scope("serve.a"):               # warm up before measuring
        pass
    tracemalloc.start()
    snap1 = tracemalloc.take_snapshot()
    for _ in range(1000):
        with tr.scope("serve.round"):
            with tr.scope("serve.engine.step"):
                pass
    snap2 = tracemalloc.take_snapshot()
    tracemalloc.stop()
    import repro.obs.trace as trace_mod
    grew = [s for s in snap2.compare_to(snap1, "lineno")
            if s.size_diff > 0
            and s.traceback[0].filename == trace_mod.__file__]
    assert not grew, grew


def test_timeline_tracer_scopes_nest_on_the_wall_clock():
    notes = []

    class Note:
        def __init__(self, name, **kw):
            notes.append((name, kw))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    tr = TimelineTracer(annotate=Note)
    t0 = wall_time()
    with tr.scope("serve.round", round=0) as outer:
        with tr.scope("serve.prefill", rid=7):
            pass
        with tr.scope("serve.engine.step"):
            pass
    t1 = wall_time()
    inner = [s for s in tr.spans if s.parent is outer]
    assert [s.name for s in inner] == ["serve.prefill", "serve.engine.step"]
    assert outer.parent is None and outer.args == {"round": 0}
    assert t0 <= outer.start <= inner[0].start <= inner[0].end \
        <= inner[1].start <= inner[1].end <= outer.end <= t1
    assert tr.children(outer) == inner
    # every scope entered the injected profiler annotation, args included
    assert notes == [("serve.round", {"round": 0}),
                     ("serve.prefill", {"rid": 7}),
                     ("serve.engine.step", {})]


def test_timeline_tracer_records_and_finishes_open_spans():
    tr = TimelineTracer()
    assert tr.enabled is True
    tr.begin("req:0", "queued", 0.0)
    tr.end("req:0", "queued", 1.0, reason="admitted")
    tr.span("adapter", "adapter.load a1", 0.5, 2.0, adapter_id=1)
    tr.instant("store", "prefetch a1", 0.25)
    tr.counter("sched", "queue_depth", 1.0, 3.0)
    tr.begin("inst:0", "decode.step", 1.0)
    tr.end("inst:0", "bogus", 1.5)          # unmatched end: dropped
    tr.finish(4.0)                          # closes the open decode.step
    by = {(s.track, s.name): s for s in tr.spans}
    assert by[("req:0", "queued")].args == {"reason": "admitted"}
    assert by[("inst:0", "decode.step")].end == 4.0
    assert all(s.start <= s.end for s in tr.spans)
    assert tr.tracks() == ["req:0", "adapter", "inst:0", "store", "sched"]
    assert not tr._open


# ---------------------------- registry unit ----------------------------- #
def test_registry_get_or_create_and_kind_mismatch():
    reg = MetricsRegistry()
    c = reg.counter("tokens_total", "tokens")
    assert reg.counter("tokens_total") is c
    c.inc(3)
    with pytest.raises(ValueError):
        c.inc(-1)
    with pytest.raises(ValueError):
        reg.gauge("tokens_total")
    h = reg.histogram("lat", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(5.0)
    assert h.bucket_counts == [1, 1] and h.count == 2
    assert reg.snapshot() == {"tokens_total": 3.0, "lat_count": 2.0,
                              "lat_sum": 5.05}


# ------------------------------- goldens -------------------------------- #
def _golden_tracer() -> TimelineTracer:
    tr = TimelineTracer()
    tr.begin("req:0", "queued", 0.0)
    tr.end("req:0", "queued", 1.0)
    tr.begin("req:0", "prefill", 1.0)
    tr.end("req:0", "prefill", 1.5)
    tr.span("adapter", "adapter.load a3", 0.5, 1.25, adapter_id=3)
    tr.instant("store", "prefetch a3", 0.25, rid=0)
    tr.counter("sched", "queue_depth", 1.0, 2.0)
    tr.begin("inst:0", "decode.step", 1.5)
    tr.finish(2.0)
    return tr


def _golden_registry() -> MetricsRegistry:
    reg = MetricsRegistry()
    reg.counter("requests_queued_total",
                "requests that entered the queue").inc(3)
    reg.gauge("queue_depth", "requests waiting for admission").set(2)
    h = reg.histogram("ttft_seconds", "queued -> first token",
                      buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 5.0, 20.0):
        h.observe(v)
    return reg


def test_perfetto_export_matches_golden():
    got = json.dumps(to_perfetto(_golden_tracer()), indent=1,
                     sort_keys=True) + "\n"
    assert got == (GOLDEN / "obs_trace_perfetto.json").read_text()


def test_prometheus_export_matches_golden():
    got = to_prometheus(_golden_registry())
    assert got == (GOLDEN / "obs_metrics.prom").read_text()


def test_jsonl_export_round_trips():
    lines = to_jsonl(_golden_tracer()).splitlines()
    evs = [json.loads(ln) for ln in lines]
    assert {e["type"] for e in evs} == {"span", "instant", "counter"}
    spans = [e for e in evs if e["type"] == "span"]
    assert all(e["start"] <= e["end"] for e in spans)


# ----------------------- schema validation helpers ---------------------- #
_STAGES = ("queued", "prefill", "decode")


def _validate_trace(tr: TimelineTracer):
    """The trace-schema contract shared by both planes."""
    assert not tr._open, "finish() must close every span"
    for s in tr.spans + tr.instants:
        assert s.start >= 0.0 and s.start <= s.end, s
    for track in tr.tracks():
        spans = tr.spans_for(track)
        if track.startswith(("req:", "inst:")):
            # virtual-time monotone + non-overlapping per track
            for a, b in zip(spans, spans[1:]):
                assert b.start >= a.end - 1e-9, (track, a, b)
        if track.startswith("req:"):
            names = [s.name for s in spans]
            assert names == list(_STAGES[:len(names)]), (track, names)
            # stage spans are CONTIGUOUS: full TTFT-window attribution
            for a, b in zip(spans, spans[1:]):
                assert b.start == pytest.approx(a.end), (track, a, b)
    for (_, _, t, _), (_, _, t2, _) in zip(tr.counters, tr.counters[1:]):
        assert t2 >= t - 1e-9


# ------------------------------ sim plane ------------------------------- #
def _sim_run(trace, **kw):
    cfg = ServeConfig(backend="sim", disaggregated=True, duration=60.0,
                      n_adapters=16, adapter_cache_slots=4, max_batch=2,
                      trace=trace, **kw)
    system = build_system(cfg, get_config("qwen3-moe-235b-a22b").reduced())
    for i in range(8):
        system.submit(prompt_len=8, adapter_id=i % 5, max_new_tokens=4,
                      arrival=float(i))
    evs = []
    while not system.backend.idle():
        evs.extend((e.time, e.rid, e.kind) for e in system.step())
    return system, evs


def test_sim_tracing_on_off_event_streams_identical():
    _, evs_off = _sim_run(False)
    system, evs_on = _sim_run(True)
    assert evs_off == evs_on
    assert all(h.state.name == "FINISHED" for h in system.handles.values())


def test_sim_trace_schema_and_virtual_time_monotone():
    system, _ = _sim_run(True)
    obs = system.observability()
    obs.perfetto()                              # finalizes open spans
    tr = obs.tracer
    _validate_trace(tr)
    assert any(t.startswith("req:") for t in tr.tracks())
    assert any(t.startswith("inst:") for t in tr.tracks())
    assert any(s.name.startswith("adapter.load") for s in tr.spans)
    assert any(s.name.startswith("prefetch") for s in tr.instants)


def test_scale_events_become_trace_instants_and_shim_survives():
    from repro.serving.api import AutoscalePolicy
    pol = AutoscalePolicy(control_interval=2.0, max_instances=4,
                          scale_down_patience=1)
    _, evs_off = _sim_run(False, autoscale=pol)
    system, evs_on = _sim_run(True, autoscale=pol)
    assert evs_off == evs_on                    # autoscale + trace: no drift
    assert system.scale_events                  # deprecated shim still fills
    control = [i for i in system.observability().tracer.instants
               if i.track == "control"]
    assert len(control) == len(system.scale_events)
    assert all(i.name.startswith("scale:") for i in control)
    reg = system.observability().registry
    assert reg.get("scale_actions_total").value == len(control)


# ----------------------------- cluster plane ---------------------------- #
@pytest.fixture(scope="module")
def cluster_setup():
    import jax
    import jax.numpy as jnp
    from repro.core.adapter import init_adapter_pool
    from repro.models import model as model_mod
    cfg = dataclasses.replace(get_config("qwen3-moe-235b-a22b").reduced(),
                              lora_targets=("gate", "up", "down"),
                              lora_rank=4)
    key = jax.random.PRNGKey(0)
    params = model_mod.init_params(cfg, key, dtype="float32")
    pool = init_adapter_pool(cfg, 4, jax.random.fold_in(key, 1), rank=4,
                             dtype=jnp.float32)
    return cfg, params, pool


SPECS = [(0, 0.0, 5, 6), (1, 0.0, 4, 4), (2, 2.0, 6, 5)]


def _cluster_system(setup, trace, paged=False, transport="host"):
    cfg, params, pool = setup
    sc = ServeConfig(backend="cluster", disaggregated=True, n_instances=1,
                     max_batch=2, max_len=32, adapter_cache_slots=4,
                     paged=paged, page_size=4, n_pages=8, prefill_chunk=8,
                     transport=transport, trace=trace)
    return build_system(sc, cfg, params=params, pool=pool)


def _cluster_run(setup, trace, paged=False, transport="host"):
    system = _cluster_system(setup, trace, paged, transport)
    handles = [system.submit(adapter_id=a, arrival=t, prompt_len=p,
                             max_new_tokens=o) for a, t, p, o in SPECS]
    system.drain()
    assert all(h.state.name == "FINISHED" for h in handles)
    return system, {h.rid: tuple(h.tokens) for h in handles}


@pytest.mark.parametrize("paged,transport",
                         [(False, "host"), (True, "fused")],
                         ids=["dense_host", "paged_fused"])
def test_cluster_tracing_on_off_tokens_bit_identical(cluster_setup, paged,
                                                     transport):
    _, toks_off = _cluster_run(cluster_setup, False, paged, transport)
    system, toks_on = _cluster_run(cluster_setup, True, paged, transport)
    assert toks_off == toks_on
    obs = system.observability()
    obs.perfetto()
    _validate_trace(obs.tracer)
    if paged:
        kv = [i for i in obs.tracer.instants if i.track == "kv"]
        assert len(kv) == len(SPECS)            # one alloc per admission
        assert all(i.args["pages"] >= 1 for i in kv)
    steps = [s for s in obs.tracer.spans if s.name == "serve.engine.step"]
    assert steps and all(s.args["rows"] >= 1 and s.duration >= 0.0
                         for s in steps)


def test_cluster_trace_covers_full_ttft_window(cluster_setup):
    """Acceptance: the queued + prefill stage spans cover >= 95% of each
    request's TTFT window, and the ttft_seconds histogram agrees with it.
    The window is timed apart from the spans, on the caller's reading of
    the same wall clock: from the start of the round that enqueued the
    request to the moment its first token reached the handle."""
    system = _cluster_system(cluster_setup, True)
    first_token, round_start = {}, {}

    def on_token(h, tok):
        first_token.setdefault(h.rid, wall_time())

    handles = [system.submit(adapter_id=a, arrival=t, prompt_len=p,
                             max_new_tokens=o, on_token=on_token)
               for a, t, p, o in SPECS]
    while not system.backend.idle():
        t = wall_time()
        for ev in system.step():
            if ev.kind == "queued":
                round_start[ev.rid] = t
    assert all(h.state.name == "FINISHED" for h in handles)
    obs = system.observability()
    trace = obs.perfetto()
    assert trace["traceEvents"]
    tr = obs.tracer
    windows = []
    for h in handles:
        spans = {s.name: s for s in tr.spans_for(f"req:{h.rid}")}
        window = first_token[h.rid] - round_start[h.rid]
        covered = spans["queued"].duration + spans["prefill"].duration
        assert round_start[h.rid] <= spans["queued"].start
        assert spans["prefill"].end <= first_token[h.rid]
        assert 0.95 * window <= covered <= window, (h.rid, covered, window)
        windows.append(window)
    # ... and the request-level TTFT metric agrees with the windows
    hist = obs.registry.get("ttft_seconds")
    assert hist.count == len(windows)
    assert 0.95 * sum(windows) <= hist.sum <= sum(windows)


def test_cluster_serve_scopes_nest_once_per_busy_instance(cluster_setup):
    """serve.* scopes are on the wall clock, nest inside their round, and
    every serve.round holds one serve.engine.step per busy instance."""
    cfg, params, pool = cluster_setup
    sc = ServeConfig(backend="cluster", disaggregated=True, n_instances=2,
                     max_batch=2, max_len=32, adapter_cache_slots=4,
                     paged=True, page_size=4, n_pages=8, prefill_chunk=8,
                     transport="fused", trace=True)
    system = build_system(sc, cfg, params=params, pool=pool)
    for a, t, p, o in SPECS + [(3, 0.0, 7, 3)]:
        system.submit(adapter_id=a, arrival=t, prompt_len=p,
                      max_new_tokens=o)
    t0 = wall_time()
    tokens_per_round = []
    while not system.backend.idle():
        evs = system.step()
        tokens_per_round.append(sum(e.kind == "token" for e in evs))
    t1 = wall_time()
    tr = system.observability().tracer
    serve = [s for s in tr.spans if s.track == "serve"
             and s.name != "serve.gc"]
    assert serve and all(t0 <= s.start <= s.end <= t1 for s in serve)
    for s in serve:                                   # proper nesting
        if s.parent is not None:
            assert s.parent.start <= s.start <= s.end <= s.parent.end
    rounds = sorted((s for s in serve if s.name == "serve.round"),
                    key=lambda s: s.start)
    assert len(rounds) == len(tokens_per_round)
    for a, b in zip(rounds, rounds[1:]):              # monotone
        assert a.end <= b.start
    for rnd, n_tok in zip(rounds, tokens_per_round):
        steps = [c for c in tr.children(rnd)
                 if c.name == "serve.engine.step"]
        assert len({c.args["iid"] for c in steps}) == len(steps)
        assert sum(c.args["rows"] for c in steps) == n_tok
        for st in steps:                  # a collection may land anywhere
            assert [c.name for c in tr.children(st)
                    if c.name != "serve.gc"] == [
                "serve.engine.prepare", "serve.transport.refresh",
                "serve.engine.dispatch", "serve.engine.sync",
                "serve.engine.emit"]
    assert any(s.name == "serve.events" and s.parent is None
               for s in serve)
    prefills = [s for s in serve if s.name == "serve.prefill"]
    assert len(prefills) == len(SPECS) + 1
    assert all(s.parent.name == "serve.admit" and
               s.args["tokens"] + s.args["padded_tokens"]
               == 8 * s.args["chunks"] for s in prefills)
    system.close()


def test_tracing_off_builds_no_scope_arguments(cluster_setup,
                                               monkeypatch):
    """With tracing off, no producer builds a scope's keyword arguments:
    the scopes that take some are skipped for the shared no-op context."""
    from repro.obs.trace import NullTracer
    calls = []

    def scope(self, name, **args):
        calls.append((name, args))
        return NO_SCOPE

    monkeypatch.setattr(NullTracer, "scope", scope)
    _cluster_run(cluster_setup, False, True, "fused")
    names = {name for name, _ in calls}
    assert {"serve.engine.prepare", "serve.engine.dispatch",
            "serve.events"} <= names
    assert not {"serve.round", "serve.engine.step",
                "serve.prefill"} & names
    assert all(args == {} for _, args in calls)


def test_garbage_collections_become_spans_until_close():
    import gc
    tr = TimelineTracer(gc_spans=True)
    gc.collect()
    spans = [s for s in tr.spans if s.name == "serve.gc"]
    assert spans and spans[-1].args["generation"] == 2
    assert spans[-1].args["collected"] >= 0
    tr.close()
    n = len(tr.spans)
    gc.collect()
    assert len(tr.spans) == n


def test_cluster_prometheus_and_perfetto_exports(cluster_setup):
    system, _ = _cluster_run(cluster_setup, True)
    obs = system.observability()
    system.summary()                            # publishes summary gauges
    text = obs.prometheus()
    for name in ("requests_finished_total", "ttft_seconds_bucket",
                 "queue_depth", "kv_slots_in_use", "cache_caches",
                 "transport_steps", "summary_n_finished"):
        assert name in text, name
    trace = obs.perfetto()
    names = {e["name"] for e in trace["traceEvents"]}
    assert {"queued", "prefill", "decode", "serve.engine.step",
            "queue_depth"} <= names
    phases = {e["ph"] for e in trace["traceEvents"]}
    assert {"X", "M", "C"} <= phases
    # every event references a declared thread track
    tids = {e["tid"] for e in trace["traceEvents"] if e["ph"] == "M"}
    assert all(e["tid"] in tids for e in trace["traceEvents"]
               if e["ph"] != "M")


# ------------------ named scopes of a described v5e compile -------------- #
@pytest.fixture(scope="module")
def one_chip():
    """One device of a described ``v5e:2x2`` topology (as in
    tests/test_tpu_compile.py): compiled for, never run on."""
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler to describe it with
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


def test_fused_decode_scope_map_on_described_v5e(one_chip):
    import jax
    import jax.numpy as jnp
    from repro.models import cache as cache_mod
    from repro.models.model import abstract_params
    from repro.transport import fused
    cfg = dataclasses.replace(get_config("qwen3-moe-235b-a22b").reduced(),
                              lora_targets=("gate", "up", "down"),
                              lora_rank=4)

    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(lambda a: s(a.shape, a.dtype),
                                    abstract_params(cfg))
    L, E, d, ff = cfg.n_layers, cfg.n_experts, cfg.d_model, cfg.d_ff
    r, M, B, ps, n_pages = 4, 4, 2, 4, 8
    view = fused.DeviceLoraView(
        s((1, L, M, E, d, 2 * r)), s((1, L, M, E, 2 * r, 2 * ff)),
        s((1, L, M, E, ff, r)), s((1, L, M, E, r, d)),
        s((8,), jnp.int32), s((1, M), jnp.int32))
    kv = jax.eval_shape(lambda: cache_mod.init_paged_cache(cfg, n_pages, ps))
    pool = s(kv["k"].shape, kv["k"].dtype)
    step = jax.jit(fused._fused_paged_fn, static_argnames=("cfg",))
    text = step.lower(params, cfg, pool, pool, s((B, n_pages // B),
                                                 jnp.int32),
                      s((B, 1), jnp.int32), s((B,), jnp.int32), view,
                      s((B,), jnp.int32), s((), jnp.float32)
                      ).compile().as_text()
    # each compiled instruction carries its scope path in its metadata,
    # which a profile's HLO keeps for the benchmark's join
    held = {}
    for op_name in re.findall(r'op_name="([^"]*)"', text):
        for part in op_name.split("/"):
            held[part] = held.get(part, 0) + 1
    for scope in ("lora_hook", "moe_experts", "attention"):
        assert held.get(scope, 0) >= 1, (scope, sorted(held))
