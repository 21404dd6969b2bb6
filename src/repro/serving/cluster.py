"""Cluster driver: N slot-engine instances under the token-level Scheduler.

This is the REAL-execution twin of ``serving/simulator.py``: the same
control plane (``Scheduler`` admission/pinning/retirement, ``LoRACache``
residency, greedy adapter placement) drives actual JAX decode steps on
``Engine`` instances instead of the analytic step-time model. Time is
virtual — every global decode round advances the clock by ``step_time`` —
so admission, layer-wise adapter loading, and SLO bookkeeping run the exact
code paths the simulator exercises, while tokens come from the model.

Both systems run end to end:

  coupled (S-LoRA)       : per-instance adapter caches, requests routed to
                           the instance owning their adapter (greedy
                           pre-assignment, paper §6.1), adapters applied
                           in-model
  disaggregated          : one shared LoRA cache mirrored into an elastic
  (InfiniLoRA)             ``ServerPool`` of LoRA-Server replicas
                           (adapter-affinity routing, delta-based residency
                           sync); any instance serves any request

Elastic provisioning: ``ClusterConfig.autoscale`` attaches an
``Autoscaler`` (paper §4.2 / Algorithm 1 run online). At each round
boundary it may resize the adapter caches, add/remove server replicas, or
add/drain LLM instances — the instance set is DYNAMIC (dict keyed by iid;
drained instances finish their in-flight work, then retire and release
their KV). Scaling must never change a request's token stream: greedy
decoding depends only on the request's own prompt, so coupled ==
disaggregated == elastic-disaggregated, enforced by test.

Requests are admitted at decode-step boundaries into a RUNNING batch
(continuous batching) and evicted the step they finish; greedy decoding is
deterministic, so for the same workload the modes must produce identical
tokens per request — the architectural equivalence claim, now measurable
under churn AND under scaling events.
"""
from __future__ import annotations

import copy
import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.configs.base import ModelConfig
from repro.core.adapter import AdapterPool
from repro.core.lora_server import LoRAServer
from repro.models.cache import pages_for
from repro.obs.clock import wall_time
from repro.obs.trace import NO_SCOPE, NULL_TRACER, Tracer
from repro.serving.autoscaler import Autoscaler, AutoscalePolicy, \
    ScaleAction, converge_replicas, pick_drain_candidate
from repro.serving.cache import LoRACache
from repro.serving.engine import Engine, EngineConfig
from repro.serving.scheduler import InstanceState, Scheduler, \
    assign_adapters_greedy
from repro.serving.server_pool import ServerPool
from repro.serving.workload import Request
from repro.store import AdapterStore
from repro.transport import make_transport


@dataclasses.dataclass
class ClusterConfig:
    n_instances: int = 2
    n_slots: int = 4                 # decode slots (max batch) per instance
    max_len: int = 64
    disaggregated: bool = False
    adapter_cache_slots: int = 8     # per instance (coupled) / shared (disagg)
    policy: str = "fcfs"
    step_time: float = 1.0           # virtual seconds per decode round
    # adapter load bandwidth; inf -> load time exactly 0, so cold adapters
    # admit the SAME round (any finite bw defers admission one round)
    host_bw: float = float("inf")
    layerwise_loading: bool = True
    max_rounds: int = 100_000
    # paged KV engine: block-pool cache + page-budget admission (see
    # serving/engine.py). n_pages=None sizes the pool to the dense-slab
    # worst case; smaller values trade admission concurrency for memory.
    paged: bool = False
    page_size: int = 8
    n_pages: Optional[int] = None
    prefill_chunk: int = 16
    # elastic provisioning: run Algorithm 1 online at round boundaries
    autoscale: Optional[AutoscalePolicy] = None
    # disaggregated hook transport plane: "host" (per-hook host dispatch,
    # 2 x n_layers round trips per decode step) or "fused" (device-resident
    # LUT + one jitted program per step; see src/repro/transport/)
    transport: str = "host"
    # per-launch cost fed to the autoscaler's TPOT-budget derate (the real
    # plane MEASURES dispatches but models their cost; 0 = no derate)
    hook_launch_us: float = 0.0
    # mesh-sharded execution plane: (data, model) device grid for the
    # disaggregated decode step — the base MoE's expert GEMMs run
    # expert-parallel over the "data" axis via shard_map (launch/mesh.py
    # ``make_serve_mesh`` + distributed/steps.py ``expert_parallel_ctx``).
    # Requires disaggregated=True (the coupled step's psum would break
    # token bit-identity). None = single-device (the default).
    mesh_shape: Optional[Tuple[int, int]] = None
    # hierarchical adapter store (disaggregated only): host-RAM tier byte
    # budget (None = unbounded, the whole universe stays host-resident),
    # disk-tier directory (None = private tempdir created on first spill),
    # and disk read bandwidth for miss pricing
    store_host_bytes: Optional[int] = None
    store_dir: Optional[str] = None
    disk_bw: float = 5e9
    # async prefetch staging + scheduler prefetch hints; None follows
    # layerwise_loading (the legacy coupling of the two knobs)
    prefetch: Optional[bool] = None
    # rank-aware hook compute: bound each row's hook contraction at its
    # adapter's TRUE rank instead of the padded pool rank. Padded lanes
    # are exact zeros, so this is bitwise-neutral on the token stream
    # (pinned by test) while pricing/telemetry see the true-rank FLOPs.
    rank_aware: bool = True

    @property
    def prefetch_on(self) -> bool:
        return self.layerwise_loading if self.prefetch is None \
            else self.prefetch


class Cluster:
    """N client instances against one adapter plane (pool of replicas or
    per-instance caches); the instance set is elastic when autoscaling."""

    def __init__(self, cfg: ModelConfig, params, ccfg: ClusterConfig,
                 pool: AdapterPool,
                 server_pool: Optional[ServerPool] = None,
                 server: Optional[LoRAServer] = None,
                 tracer: Optional[Tracer] = None):
        # span tracer (repro.obs): this plane stamps the WALL clock
        # (obs.clock.wall_time) — serve.* scopes around each phase of a
        # round, instants, counters and adapter loads alike.
        # NULL_TRACER = record nothing.
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.mesh_ctx = None
        if ccfg.mesh_shape is not None:
            if not ccfg.disaggregated:
                raise ValueError(
                    "mesh_shape requires disaggregated=True: the coupled "
                    "step's allgather MoE reassociates floats under a "
                    "mesh, breaking the token bit-identity invariant")
            from repro.distributed.steps import expert_parallel_ctx, \
                shard_serve_params
            from repro.launch.mesh import make_serve_mesh
            data, model = ccfg.mesh_shape
            if data < 1 or model < 1:
                raise ValueError(
                    f"mesh_shape dims must be positive, got "
                    f"{ccfg.mesh_shape}")
            mesh = make_serve_mesh(data, model)
            self.mesh_ctx = expert_parallel_ctx(mesh, cfg)
            if self.mesh_ctx is not None:
                params = shard_serve_params(params, self.mesh_ctx)
            # ctx None (1-device mesh / E not shardable) -> plain path:
            # trivially bit-identical, nothing to place
        if ccfg.disaggregated:
            if server_pool is None and server is not None:
                # legacy single-server callers: wrap into a 1-replica pool,
                # cloning the server's config as the replica factory so the
                # autoscaler's add_replica still works against the shim
                scfg = server.scfg
                dtype = next(iter(server.pool.values())).dtype
                server_pool = ServerPool(
                    [server],
                    factory=lambda: LoRAServer(cfg, scfg, dtype=dtype))
            if server_pool is None:
                raise ValueError(
                    "disaggregated mode needs a ServerPool (server_pool=) "
                    "or a legacy LoRAServer (server=)")
            if server_pool.total_slots < ccfg.adapter_cache_slots:
                # the shared LoRACache mirrors into the replicas' slot
                # pools, so a too-small pool could hit "cache full"
                # mid-run. Duplicated pools bound by the smallest replica
                # (worst case routes everything to it); partitioned pools
                # bound by the aggregate (per-home admission enforces each
                # replica's share).
                kind = "aggregate" if server_pool.partitioned else "replica"
                raise ValueError(
                    f"ServerPool {kind} capacity {server_pool.total_slots} "
                    f"< adapter_cache_slots={ccfg.adapter_cache_slots}")
        self.cfg = cfg
        self.ccfg = ccfg
        self.pool = pool
        self.params = params
        self.server_pool = server_pool if ccfg.disaggregated else None
        if self.server_pool is not None:
            self.server_pool.set_rank_aware(ccfg.rank_aware)
        # hierarchical adapter store: host/disk tiers + async staging + the
        # dynamic register/unregister lifecycle. Disaggregated-only — the
        # coupled path gathers adapters from the static pool inside the
        # model, so its universe is frozen at startup by construction.
        self.store: Optional[AdapterStore] = None
        if ccfg.disaggregated:
            self.store = AdapterStore(
                cfg, pool, host_bytes=ccfg.store_host_bytes,
                store_dir=ccfg.store_dir, host_bw=ccfg.host_bw,
                disk_bw=ccfg.disk_bw, prefetch=ccfg.prefetch_on)
        # ONE transport for the whole cluster: every instance's engine
        # shares its stats ledger (system-level launch counts) and, on the
        # fused plane, its device-resident LUT/pool view
        self.transport = None
        if ccfg.disaggregated:
            self.transport = make_transport(ccfg.transport, self.server_pool,
                                            n_adapters=pool.n,
                                            mesh_ctx=self.mesh_ctx,
                                            tracer=self.tracer)
        self._ecfg = EngineConfig(max_len=ccfg.max_len, n_slots=ccfg.n_slots,
                                  paged=ccfg.paged, page_size=ccfg.page_size,
                                  n_pages=ccfg.n_pages,
                                  prefill_chunk=ccfg.prefill_chunk)
        # engines are built by open() — every entrypoint (run(), the front
        # door's ClusterBackend) opens before touching them, so an eager
        # build here would just be thrown away
        self.engines: Dict[int, Engine] = {}
        # session state (built by open(); run() opens its own)
        self.sched: Optional[Scheduler] = None
        self._instances: Dict[int, InstanceState] = {}
        self._caches: Dict[int, LoRACache] = {}
        self._cache_slots = ccfg.adapter_cache_slots
        self._scaler: Optional[Autoscaler] = None
        self._next_iid = ccfg.n_instances
        self.tokens: Dict[int, List[int]] = {}
        self._reqs: Dict[int, Request] = {}
        self._pending: List[Request] = []
        self._pi = 0
        self.rnd = 0

    def _new_engine(self) -> Engine:
        return Engine(self.cfg, self.params, self._ecfg, pool=self.pool,
                      server=self.server_pool,
                      transport=self.transport or "host",
                      mesh_ctx=self.mesh_ctx, tracer=self.tracer)

    def _pool_capacity(self) -> int:
        """The server pool's physical cache-slot bound: aggregate capacity
        when partitioned (per-home admission enforces each replica's
        share), smallest replica otherwise (worst-case affinity skew)."""
        return self.server_pool.total_slots if self.server_pool.partitioned \
            else self.server_pool.min_slots

    def _set_cache_partition(self) -> None:
        """Install (or refresh) the shared cache's per-home residency
        bounds from the partitioned pool's current replica set."""
        self._caches[-1].set_partition(self.server_pool.replica_for,
                                       self.server_pool.partition_caps())

    # ------------------------------------------------------------------ #
    def _prompt(self, req: Request) -> np.ndarray:
        """Deterministic prompt tokens for a request: either the tokens it
        carries (served verbatim — feasibility is checked up front in
        ``run``, never silently truncated), or a seeded draw from its rid —
        identical across modes so token-equivalence is meaningful. Synthetic
        prompts are clamped so prompt + output fit the KV allocation."""
        if req.prompt:
            return np.asarray(req.prompt, np.int32).reshape(-1)
        room = self.ccfg.max_len - req.output_len - 1
        plen = max(1, min(req.prompt_len, room))
        rng = np.random.default_rng(7919 + req.rid)
        return rng.integers(0, self.cfg.vocab_size, plen).astype(np.int32)

    def _sync_pool(self) -> None:
        """Delta-based residency mirror: reconcile the replicas' slot
        tables against only the adapter ids the shared cache mutated since
        the last sync (``LoRACache.dirty``), instead of the pre-pool full
        rescan of every resident adapter every round. Uploads stage
        through the adapter store (consuming async-prefetched results and
        promoting disk-tier adapters), bitwise identical to the direct
        pool extraction it replaces."""
        self.server_pool.sync(self._caches[-1],
                              tensors_fn=self.store.server_tensors,
                              rank_fn=self.store.rank_of)

    # ------------------------------------------------------------------ #
    # incremental session API (serving/api.py front door)                 #
    # ------------------------------------------------------------------ #
    def validate(self, req: Request) -> None:
        """Admission-contract checks, raised BEFORE a request enters the
        session (the front door turns these into REJECTED handles)."""
        ccfg = self.ccfg
        # engine feasibility: plen + output_len <= max_len + 1, plen >= 1
        # (the KV-capacity bound the admission contract promises) —
        # reject up front rather than crash mid-run at the engine guard.
        # Caller-supplied prompts are served verbatim, so they must fit;
        # synthetic prompts are clamped in _prompt down to one token.
        plen = len(req.prompt) if req.prompt else 1
        if plen + req.output_len > ccfg.max_len + 1:
            raise ValueError(
                f"request {req.rid}: prompt_len {plen} + output_len "
                f"{req.output_len} cannot fit a max_len={ccfg.max_len} "
                f"slot")
        if self.store is not None:
            # dynamic universe: any id the store currently knows is legal
            if not self.store.has(req.adapter_id):
                raise ValueError(
                    f"request {req.rid}: adapter_id {req.adapter_id} is "
                    f"not registered in the adapter store")
        elif not 0 <= req.adapter_id < self.pool.n:
            # out-of-range ids would be silently clamped by the gather
            # kernels to the last adapter's weights
            raise ValueError(
                f"request {req.rid}: adapter_id {req.adapter_id} outside "
                f"pool of {self.pool.n}")
        if ccfg.paged:
            need = pages_for(int(self._prompt(req).shape[0])
                             + req.output_len - 1, ccfg.page_size)
            budget = next(iter(self.engines.values())).total_pages
            if need > budget:
                raise ValueError(
                    f"request {req.rid}: needs {need} KV pages but the "
                    f"pool has {budget} — it could never be admitted")

    def open(self, requests: Sequence[Request] = ()) -> None:
        """Start a serving session: build the scheduler/cache control plane.
        ``requests``, when known up front (the legacy batch path), seeds the
        coupled-mode greedy adapter->instance assignment with the true
        per-adapter load; a streaming session assigns from uniform weights
        over the pool."""
        ccfg = self.ccfg
        n_adapters = max(self.pool.n,
                         max((r.adapter_id for r in requests), default=0) + 1)
        self._instances = {i: InstanceState(i, ccfg.n_slots)
                           for i in range(ccfg.n_instances)}
        self.engines = {i: self._new_engine()
                        for i in range(ccfg.n_instances)}
        self._next_iid = ccfg.n_instances
        self._cache_slots = ccfg.adapter_cache_slots
        if ccfg.disaggregated:
            self._caches = {-1: self._mk_cache()}
            if self.server_pool.partitioned:
                self._set_cache_partition()
            owner = None
        else:
            counts = np.bincount([r.adapter_id for r in requests],
                                 minlength=n_adapters).astype(float)
            if not len(requests):
                counts += 1.0           # uniform expected load
            owner = assign_adapters_greedy(n_adapters, counts,
                                           ccfg.n_instances)
            self._caches = {i: self._mk_cache()
                            for i in range(ccfg.n_instances)}
        kv_pages = kv_need = None
        if ccfg.paged:
            # a resident request's page footprint: prompt positions plus one
            # page-row per decoded token (the last emitted token is never
            # written, hence -1); memoized by rid — admit() consults it for
            # every resident request each round
            kv_pages = {i: self.engines[i].total_pages
                        for i in range(ccfg.n_instances)}
            self._need_by_rid: Dict[int, int] = {}

            def kv_need(r: Request) -> int:
                if r.rid not in self._need_by_rid:
                    plen = int(self._prompt(r).shape[0])
                    self._need_by_rid[r.rid] = pages_for(
                        plen + r.output_len - 1, ccfg.page_size)
                return self._need_by_rid[r.rid]
        self.sched = Scheduler(list(self._instances.values()), self._caches,
                               owner, policy=ccfg.policy,
                               shared_cache=ccfg.disaggregated,
                               kv_pages=kv_pages, kv_page_need=kv_need)
        self._scaler = None
        if ccfg.autoscale is not None:
            pol = ccfg.autoscale
            if self.server_pool is not None and \
                    pol.max_cache_slots > self._pool_capacity():
                # cap the policy at the pool's physical slot capacity —
                # otherwise the control loop would chase an unreachable
                # cache target, re-emitting the same resize action forever
                pol = dataclasses.replace(
                    pol, max_cache_slots=self._pool_capacity())
            self._scaler = Autoscaler(pol, self.cfg, max_batch=ccfg.n_slots,
                                      has_server=self.server_pool is not None,
                                      transport=ccfg.transport,
                                      hook_launch_us=ccfg.hook_launch_us)
        self.tokens: Dict[int, List[int]] = {}
        self._reqs: Dict[int, Request] = {}
        self._pending: List[Request] = []
        self._pi = 0
        self.rnd = 0

    def _mk_cache(self) -> LoRACache:
        return LoRACache(self._cache_slots, self.pool.bytes_per_adapter(),
                         self.cfg.n_layers, host_bw=self.ccfg.host_bw,
                         layerwise=self.ccfg.layerwise_loading,
                         prefetch=self.ccfg.prefetch_on,
                         load_seconds_fn=self.store.load_seconds
                         if self.store is not None else None,
                         tracer=self.tracer, clock=wall_time)

    @property
    def now(self) -> float:
        """Virtual time of the NEXT round boundary."""
        return self.rnd * self.ccfg.step_time

    def submit(self, req: Request) -> Request:
        """Add one request to the open session (takes ownership of ``req``;
        the legacy ``run`` copies before submitting). May be called mid-run:
        the request joins the queue at the next round boundary."""
        if self.sched is None:
            raise RuntimeError("Cluster.open() before submit()")
        if req.rid in self._reqs:
            raise ValueError(f"rid {req.rid} already submitted")
        self.validate(req)
        self._reqs[req.rid] = req
        self.tokens[req.rid] = []
        # keep pending sorted by (arrival, rid); mid-run submissions land
        # after the consumed prefix so past arrivals enqueue next round
        lo = self._pi
        while lo < len(self._pending) and \
                (self._pending[lo].arrival, self._pending[lo].rid) <= \
                (req.arrival, req.rid):
            lo += 1
        self._pending.insert(lo, req)
        return req

    def cancel(self, rid: int) -> bool:
        """Cancel a submitted request at a round boundary: release its
        scheduler state (queue slot or running set + adapter pin) and its
        engine slot AND KV pages mid-flight. Partial tokens stay in
        ``tokens[rid]``; the request never gets a finish stamp. Returns
        False if the rid is unknown or already terminal."""
        req = self._reqs.get(rid)
        if req is None or req.finish >= 0 or req.cancelled:
            return False
        where = self.sched.cancel(req, self.now)   # also sets req.cancelled
        if where is None:
            # still pending (future arrival): drop it from the arrival list,
            # otherwise idle() waits (spinning empty rounds) until its
            # arrival time just to skip it
            for i in range(self._pi, len(self._pending)):
                if self._pending[i].rid == rid:
                    del self._pending[i]
                    break
        for eng in self.engines.values():
            if eng.has_request(rid):
                eng.evict_request(rid)      # slot + pages come back NOW
                break
        return True

    # ------------------------- elastic control ------------------------- #
    def _n_admitting(self) -> int:
        return sum(1 for i in self._instances.values()
                   if i.alive and not i.draining)

    def _run_control(self, now: float) -> List[ScaleAction]:
        if self._scaler is None or not self._scaler.due(now):
            return []
        in_flight = sum(i.batch for i in self._instances.values()
                        if i.alive)
        mean_rank = None
        if self.transport is not None and self.ccfg.rank_aware:
            observed = self.transport.stats.mean_active_rank()
            mean_rank = observed if observed > 0 else None
        actions = self._scaler.control(
            now, in_flight=in_flight, queued=self.sched.queue_len(),
            cache_slots=self._cache_slots,
            n_instances=self._n_admitting(),
            n_replicas=self.server_pool.n_replicas
            if self.server_pool else 1,
            host_hit_rate=self.store.host_hit_rate()
            if self.store else None,
            miss_cost_ratio=self.store.miss_cost_ratio()
            if self.store else 1.0,
            mean_active_rank=mean_rank)
        for act in actions:
            self._apply_action(act, now)
        return actions

    def _apply_action(self, act: ScaleAction, now: float) -> None:
        pol = self._scaler.policy if self._scaler else AutoscalePolicy()
        if act.kind == "resize_cache":
            target = act.target
            if self.server_pool is not None:
                # physical slot tables bound the policy knob (defensive:
                # open() already caps the autoscaler's max at the pool's
                # capacity — aggregate when partitioned)
                target = min(target, self._pool_capacity())
            self._cache_slots = max(target, 1)
            for c in self._caches.values():
                c.resize(self._cache_slots, now)
            if self.server_pool is not None:
                # flush the shrink's evictions into the replica slot pools
                # NOW — waiting for the next admission-triggered sync would
                # leave freed adapters' weights resident indefinitely on a
                # quiet (or all-hit) stream
                self._sync_pool()
        elif act.kind == "add_instance":
            while self._n_admitting() < min(act.target, pol.max_instances):
                self._add_instance(now)
        elif act.kind == "drain_instance":
            floor = max(act.target, pol.min_instances, 1)
            while self._n_admitting() > floor:
                cand = pick_drain_candidate(self._instances.values(),
                                            self.sched.queues)
                self.sched.drain_instance(cand.iid, now)
        elif act.kind in ("add_replica", "remove_replica"):
            if self.server_pool is None:
                return              # coupled plane has no server replicas
            if converge_replicas(self.server_pool, act.target):
                if self.server_pool.partitioned:
                    # the affinity map changed, so per-home residency
                    # bounds change with it: evict overflow out of any
                    # now-over-capacity home BEFORE the sync mirrors
                    # residency into the (smaller) replica slot tables
                    self._caches[-1].repartition(
                        self.server_pool.replica_for,
                        self.server_pool.partition_caps(), now)
                # re-route NOW: running requests' adapters must sit on
                # their (new) affinity replicas before the next decode step
                self._sync_pool()

    def _add_instance(self, now: float) -> int:
        iid = self._next_iid
        self._next_iid += 1
        inst = InstanceState(iid, self.ccfg.n_slots)
        self._instances[iid] = inst
        eng = self._new_engine()
        self.engines[iid] = eng
        cache = None if self.ccfg.disaggregated else self._mk_cache()
        pop = None
        if not self.ccfg.disaggregated and self._scaler is not None:
            pop = self._scaler.popularity(self.pool.n)
        self.sched.add_instance(
            inst, cache=cache, popularity=pop,
            kv_budget=eng.total_pages if self.ccfg.paged else None, now=now)
        return iid

    def _retire_drained(self) -> List[int]:
        """Fully remove drained-dry instances: a long-lived elastic session
        cycles scale-out/scale-in many times, and keeping dead engines and
        instance records around would leak memory AND per-round scan work
        (iids are never reused, so removal is unambiguous)."""
        retired = []
        for iid, inst in self._instances.items():
            if (inst.draining and inst.alive and inst.batch == 0
                    and not self.engines[iid].active_rids()):
                inst.alive = False
                self.engines[iid].release_kv()
                retired.append(iid)
        for iid in retired:
            del self.engines[iid]
            del self._instances[iid]
            self.sched.instances.pop(iid, None)
            self.sched.queues.pop(iid, None)
            if self.sched.kv_pages is not None:
                self.sched.kv_pages.pop(iid, None)
            self._caches.pop(iid, None)
        return retired

    # ------------------------------------------------------------------ #
    def step_round(self) -> Dict:
        """Advance ONE global decode round: run the autoscaler control loop
        (if attached), enqueue due arrivals, admit at the step boundary
        (least-loaded instance first), run one engine step per busy
        instance, retire finishers and fully-drained instances. Returns the
        round report: {"now", "step_end", "enqueued", "admitted", "tokens":
        {rid: tok}, "finished", "scale", "idle"} — the per-round token
        stream the front door streams from — and, while tracing, "wall":
        the wall-clock moments the front door stamps its events with
        ({"control", "enqueue", "admit": {rid: t}, "end"}).

        With tracing on, each phase is a ``serve.*`` scope on the wall
        clock: ``serve.round`` around ``serve.control``,
        ``serve.enqueue``, ``serve.admit`` (one ``serve.prefill`` per
        request, ``serve.residency_sync``), one ``serve.engine.step`` per
        busy instance and ``serve.complete``."""
        tr = self.tracer
        with tr.scope("serve.round", round=self.rnd) if tr.enabled \
                else NO_SCOPE:
            return self._step_round()

    def _step_round(self) -> Dict:
        ccfg = self.ccfg
        tr = self.tracer
        traced = tr.enabled
        wall: Optional[Dict] = {"admit": {}} if traced else None
        now = self.now
        with tr.scope("serve.control"):
            if traced:
                wall["control"] = wall_time()
            if self.store is not None:
                # land async-staged adapters at the round boundary, BEFORE
                # any sync this round consumes them (main thread only)
                self.store.drain_prefetched()
            scale_actions = self._run_control(now)
        enqueued: List[Request] = []
        with tr.scope("serve.enqueue"):
            if traced:
                wall["enqueue"] = wall_time()
            while self._pi < len(self._pending) and \
                    self._pending[self._pi].arrival <= now:
                r = self._pending[self._pi]
                self._pi += 1
                if not r.cancelled:         # cancelled while still pending
                    self.sched.enqueue(r, now)
                    if self.store is not None:
                        # start the REAL staging (disk read + CPU fusion) at
                        # arrival, overlapped with this round's decode; the
                        # cache's prefetch_hint (inside enqueue) starts the
                        # virtual-time load clock in parallel
                        self.store.prefetch(r.adapter_id)
                        if traced:
                            tr.instant("store", f"prefetch a{r.adapter_id}",
                                       wall_time(), rid=r.rid,
                                       adapter_id=r.adapter_id)
                    if self._scaler is not None:
                        self._scaler.observe_arrival(now, r.adapter_id)
                    enqueued.append(r)
        # admission at the step boundary, least-loaded instance first
        admitted_all: List[Request] = []
        with tr.scope("serve.admit"):
            for iid in sorted(self.engines,
                              key=lambda i: (self._instances[i].batch, i)):
                admitted = self.sched.admit(iid, now)
                if admitted and ccfg.disaggregated:
                    with tr.scope("serve.residency_sync"):
                        self._sync_pool()
                for r in admitted:
                    if traced:
                        wall["admit"][r.rid] = wall_time()
                    self.engines[iid].add_request(r.rid, self._prompt(r),
                                                  r.adapter_id)
                    if traced and self.ccfg.paged:
                        tr.instant("kv", f"kv.alloc r{r.rid}", wall_time(),
                                   rid=r.rid, iid=iid,
                                   pages=self._need_by_rid.get(r.rid))
                admitted_all.extend(admitted)
            if admitted_all and self.transport is not None:
                # install the new residency on the device now, not inside
                # the first decode step that needs it
                with tr.scope("serve.residency_sync"):
                    self.transport.refresh()
        # one decode step per busy instance; requests admitted above are
        # already in the running batch (continuous batching)
        step_end = (self.rnd + 1) * ccfg.step_time
        busy = False
        round_tokens: Dict[int, int] = {}
        finished: List[Request] = []
        for iid in sorted(self.engines):
            eng = self.engines[iid]
            active = eng.active_rids()
            if not active:
                continue
            busy = True
            with tr.scope("serve.engine.step", iid=iid, rows=len(active)) \
                    if traced else NO_SCOPE:
                for rid, tok in eng.step().items():
                    self.tokens[rid].append(tok)
                    round_tokens[rid] = tok
            with tr.scope("serve.complete"):
                for r in self.sched.step_complete(iid, step_end):
                    eng.evict_request(r.rid)
                    finished.append(r)
                    if self._scaler is not None:
                        self._scaler.observe_finish(step_end,
                                                    r.finish - r.arrival)
        if traced:
            wall["end"] = wall_time()
        with tr.scope("serve.complete"):
            self._retire_drained()
        self.rnd += 1
        if traced:
            tr.counter("sched", "queue_depth", wall_time(),
                       float(self.sched.queue_len()))
        idle = (not busy and self._pi >= len(self._pending)
                and self.sched.queue_len() == 0)
        rep = {"now": now, "step_end": step_end, "enqueued": enqueued,
               "admitted": admitted_all, "tokens": round_tokens,
               "finished": finished, "scale": scale_actions, "idle": idle}
        if traced:
            rep["wall"] = wall
        return rep

    def idle(self) -> bool:
        """No running work, no queued work, no pending arrivals."""
        if self.sched is None:
            return True
        return (self._pi >= len(self._pending)
                and self.sched.queue_len() == 0
                and not any(eng.active_rids()
                            for eng in self.engines.values()))

    def cache_stats(self) -> Dict:
        """Device-tier counters per cache (-1 = the shared disagg cache)
        plus the adapter store's host/disk tier telemetry."""
        return {"caches": {k: c.stats() for k, c in self._caches.items()},
                "store": self.store.stats() if self.store else {}}

    # --------------------- dynamic adapter lifecycle -------------------- #
    def load_adapter(self, adapter_id: int, tensors, *,
                     alpha: Optional[float] = None) -> int:
        """Register a new adapter mid-run (vLLM-style dynamic load):
        validates shapes/rank against the model config, then makes the id
        immediately targetable by requests. Disaggregated-only. Returns
        the adapter's rank."""
        if self.store is None:
            raise ValueError(
                "dynamic adapter load requires the disaggregated plane "
                "(the coupled path gathers from the static pool in-model)")
        return self.store.register(adapter_id, tensors, alpha=alpha)

    def unload_adapter(self, adapter_id: int) -> None:
        """Remove an adapter from every tier. Refused while any submitted
        request still references it (queued, running, or pinned) — the
        eviction would yank weights out from under in-flight decode."""
        if self.store is None:
            raise ValueError(
                "dynamic adapter unload requires the disaggregated plane")
        if not self.store.has(adapter_id):
            raise ValueError(f"adapter {adapter_id} is not registered")
        for r in self._reqs.values():
            if r.adapter_id == adapter_id and r.finish < 0 \
                    and not r.cancelled:
                raise ValueError(
                    f"adapter {adapter_id} is in use by unfinished "
                    f"request {r.rid}")
        cache = self._caches.get(-1)
        if cache is not None:
            cache.invalidate(adapter_id)   # raises if somehow pinned
            # flush the eviction into the replica slot tables NOW: the
            # fused transport's residency fingerprint (pool version +
            # replica mutations) must stop mapping this id before any
            # future decode step
            self._sync_pool()
        self.store.unregister(adapter_id)

    def close(self) -> None:
        """Tear down the adapter store (prefetch thread + owned tempdir)."""
        if self.store is not None:
            self.store.close()

    def kv_stats(self) -> Dict[int, Dict]:
        return {i: eng.kv_stats() for i, eng in self.engines.items()}

    def queue_depth(self) -> int:
        """Requests waiting for admission (0 before open())."""
        return self.sched.queue_len() if self.sched is not None else 0

    def transport_stats(self) -> Dict:
        """System-level launch accounting of the disaggregated transport
        (every engine bills the one shared transport). Empty in coupled
        mode — there the whole step is a single jit by construction."""
        return self.transport.stats.as_dict() if self.transport else {}

    def scale_history(self) -> List[Dict]:
        """The autoscaler's per-control-tick record (empty when static)."""
        return list(self._scaler.history) if self._scaler else []

    # ------------------------------------------------------------------ #
    def run(self, requests: Sequence[Request]) -> Dict:
        """Serve ``requests`` to completion (or ``max_rounds``): returns
        {"tokens": {rid: [token, ...]}, "requests": ..., "rounds": n}.

        Legacy batch entrypoint, now a thin loop over the session API
        (``open``/``submit``/``step_round``). The caller's Request objects
        are not mutated — runtime fields (first_token/finish/...) land on
        the copies in ``out["requests"]``, so one request list can be
        reused across runs/modes."""
        requests = [copy.copy(r) for r in requests]
        self.open(requests)
        for r in requests:
            self.submit(r)      # validates each; all submits precede any
            #                     stepping, so a bad batch rejects up front
        while self.rnd < self.ccfg.max_rounds:
            if self.step_round()["idle"]:
                break
        unfinished = [r.rid for r in requests
                      if r.finish < 0 and not r.cancelled]
        if unfinished:
            # never return silently-truncated token streams (they would make
            # cross-mode equality checks pass trivially on empty dicts)
            raise RuntimeError(
                f"cluster run ended after {self.rnd} rounds with unfinished "
                f"requests {unfinished} (queue={self.sched.queue_len()}) — "
                f"adapter cache too small or max_rounds exhausted?")
        out = {"tokens": self.tokens, "requests": list(requests),
               "rounds": self.rnd, "cache_stats": self.cache_stats()}
        if self.ccfg.paged:
            out["kv_stats"] = self.kv_stats()
        if self._scaler is not None:
            out["scale_history"] = self.scale_history()
        return out
