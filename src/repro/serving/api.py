"""One serving front door: ``ServeConfig`` -> ``Backend`` -> ``RequestHandle``.

The repo's execution planes — the analytic discrete-event simulator
(``serving/simulator.py``) and the real JAX slot-engine cluster
(``serving/cluster.py``) — used to be wired by hand through three
overlapping configs (``SimConfig`` / ``ClusterConfig`` / ``EngineConfig``).
This module is the single request-level frontend over both:

    ServeConfig ──> build_system(cfg, model, ...) ──> ServeSystem
                                                          │ submit()
                                                          ▼
                  Backend (protocol)                 RequestHandle
                  ├── SimBackend    (analytic plane) states, tokens,
                  └── ClusterBackend (real JAX plane) cancel(), iter()

Request lifecycle (identical on both planes, so ``metrics.summarize``
observes the same thing either way):

    QUEUED ──> PREFILLING ──> DECODING ──> FINISHED
      │             │             │
      └──────────── ┴──── cancel()┴──────> CANCELLED
    submit() that violates the admission contract ───> REJECTED

Streaming: every decoded token reaches the handle the round it is produced
— consume via ``handle.on_token(cb)`` or ``for tok in handle`` (the
iterator pumps the system). The analytic plane emits token *events* with
``token=None`` (it models time, not token ids).

Cancellation (``handle.cancel()``): takes effect at the next round/event
boundary; the decode slot, the KV pages, and the scheduler's adapter pin
all come back immediately (``ServeSystem.kv_stats`` returns to its
pre-admission values), and the request is never counted in
``Summary.n_finished``.

Migration from the legacy entrypoints (kept working as shims):

    Engine.prefill/decode  -> build_system(ServeConfig(backend="cluster"))
    Cluster(...).run(reqs) -> system.submit_workload(reqs); system.drain()
    simulator.simulate     -> ServeConfig(backend="sim"); system.summary()
    SimConfig/ClusterConfig -> ServeConfig.from_sim / ServeConfig.from_cluster
"""
from __future__ import annotations

import dataclasses
import enum
import itertools
from typing import Callable, Dict, Iterator, List, Optional, Protocol, \
    Sequence, Tuple

import jax

from repro.configs.base import ModelConfig
from repro.core.cost_model import Hardware, V5E
from repro.obs.clock import wall_time
from repro.obs.hub import Observability, ObservabilityHub
from repro.obs.trace import NULL_TRACER, TimelineTracer
from repro.serving import metrics
from repro.serving.autoscaler import Autoscaler, AutoscalePolicy, ScaleAction
from repro.serving.cluster import Cluster, ClusterConfig
from repro.serving.engine import EngineConfig
from repro.serving.metrics import Summary
from repro.serving.server_pool import ServerPool
from repro.serving.simulator import SimConfig, Simulation
from repro.serving.workload import Request
from repro.store import AdapterStore
from repro.transport import TransportStats

__all__ = [
    "ServeConfig", "Backend", "SimBackend", "ClusterBackend",
    "ServeSystem", "RequestHandle", "RequestState", "Event",
    "SLOClass", "INTERACTIVE", "BATCH", "TERMINAL_STATES",
    "build_system", "Request", "Summary",
    "AutoscalePolicy", "Autoscaler", "ScaleAction", "ServerPool",
    "TransportStats", "AdapterStore", "Observability",
]


# --------------------------- request lifecycle --------------------------- #
class RequestState(enum.Enum):
    """Lifecycle state of one submitted request (identical on both
    planes): QUEUED -> PREFILLING -> DECODING -> FINISHED, with CANCELLED
    reachable from any live state and REJECTED terminal at submit()."""
    QUEUED = "queued"
    PREFILLING = "prefilling"
    DECODING = "decoding"
    FINISHED = "finished"
    CANCELLED = "cancelled"
    REJECTED = "rejected"


TERMINAL_STATES = frozenset({RequestState.FINISHED, RequestState.CANCELLED,
                             RequestState.REJECTED})


@dataclasses.dataclass(frozen=True)
class Event:
    """One observable lifecycle step, identical across backends. Scaling
    events use ``rid=-1`` and ``kind="scale:<action>"`` so benchmarks can
    plot SLO attainment against replica/instance count over time."""
    time: float
    rid: int
    kind: str                    # queued|prefill|token|finished|cancelled
    #                              |scale:<action> (autoscaler, rid=-1)
    token: Optional[int] = None  # real token id (cluster) / None (sim)
    detail: Optional[str] = None  # scale events: the autoscaler's reason
    # wall-clock stamp (obs.clock.wall_time) of the step that produced
    # the event: set on the cluster plane while tracing, else None
    wall: Optional[float] = None


@dataclasses.dataclass(frozen=True)
class SLOClass:
    """Per-request latency class (paper §6.1 SLOs are the default)."""
    name: str
    ttft_slo: float
    tpot_slo: float


INTERACTIVE = SLOClass("interactive", metrics.TTFT_SLO, metrics.TPOT_SLO)
BATCH = SLOClass("batch", 4 * metrics.TTFT_SLO, 4 * metrics.TPOT_SLO)


# ------------------------------ ServeConfig ------------------------------ #
@dataclasses.dataclass
class ServeConfig:
    """The one serving config: derives the legacy ``EngineConfig`` /
    ``ClusterConfig`` / ``SimConfig`` triplet instead of repeating their
    overlapping knobs at every call site."""
    # execution plane
    backend: str = "cluster"        # "cluster" (real JAX) | "sim" (analytic)
    disaggregated: bool = False
    # disaggregated hook transport: "host" = per-hook host dispatch
    # (2 x n_layers round trips per decode step), "fused" = GPU-initiated
    # plane (device-resident adapter->slot LUT, the whole decode step as
    # ONE jitted program; see src/repro/transport/). Token streams are
    # bit-identical across both — only the launch count (and on the sim
    # plane the modeled launch tail) differs.
    transport: str = "host"
    # capacity (previously triplicated across the three configs)
    n_instances: int = 1
    max_batch: int = 4              # decode slots per instance
    max_len: int = 64               # KV rows per slot
    adapter_cache_slots: int = 8    # per instance (coupled) / shared (disagg)
    policy: str = "fcfs"            # or "sjf" (oracle output lengths)
    # KV layout (cluster plane)
    paged: bool = False
    page_size: int = 8
    n_pages: Optional[int] = None
    prefill_chunk: int = 16
    # timing / adapter loading
    step_time: float = 1.0          # cluster: virtual seconds per round
    host_bw: float = float("inf")   # cluster: adapter load bandwidth
    layerwise_loading: bool = True
    max_rounds: int = 100_000
    # hierarchical adapter store (disaggregated only): host-RAM tier byte
    # budget (None = unbounded — the whole adapter universe stays
    # host-resident, the pre-store behavior); adapters beyond the budget
    # live on the disk tier and pay a disk read on top of the upload
    store_host_bytes: Optional[int] = None
    # disk-tier directory (cluster plane; None = private tempdir created
    # on first spill) and disk read bandwidth for miss pricing
    store_dir: Optional[str] = None
    disk_bw: float = 5e9
    # async prefetch staging + scheduler prefetch hints at request
    # arrival; None follows layerwise_loading (the legacy coupling)
    prefetch: Optional[bool] = None
    # elastic provisioning (both planes): LoRA-Server replica count at
    # start, plus the online Algorithm-1 control loop when ``autoscale``
    # carries an AutoscalePolicy (None = static provisioning)
    server_replicas: int = 1
    autoscale: Optional[AutoscalePolicy] = None
    # analytic plane (sim backend) only
    gpus_per_instance: int = 8
    server_gpus: int = 8
    placement_x: Optional[int] = None
    duration: float = 300.0
    overlap: bool = True
    fast_kernels: bool = True
    slow_kernel_eff_scale: float = 2.8  # generic-kernel penalty (ablations)
    protocol: str = "push"
    hw: Hardware = V5E
    lora_rank: Optional[int] = None
    zipf_s: float = 1.2
    n_adapters: int = 512
    step_overhead: float = 0.004
    # per-launch hook dispatch cost: prices the sim plane's launch tail
    # and derates the autoscaler's TPOT budget on BOTH planes (0 = off)
    hook_launch_us: float = 0.0
    # mesh-sharded execution plane (cluster backend, disaggregated only):
    # (data, model) device grid. The base MoE's expert GEMMs run
    # expert-parallel over "data" via shard_map, the ServerPool's LoRA slot
    # tables are PARTITIONED across replicas (each holds its affinity share
    # instead of a full duplicate), and both transports run under the mesh
    # — token streams stay bit-identical to single-device execution. On
    # CPU, multiple devices need XLA_FLAGS=
    # --xla_force_host_platform_device_count=N before jax initializes.
    mesh_shape: Optional[Tuple[int, int]] = None
    failures: Tuple[Tuple[float, int], ...] = ()
    recoveries: Tuple[Tuple[float, int], ...] = ()
    stragglers: Tuple[Tuple[float, int, float], ...] = ()
    straggler_mitigation: bool = True
    # rank-aware hook compute (both planes): bound each row's LoRA
    # contraction/pricing at its adapter's TRUE rank instead of the padded
    # pool rank. Bitwise-neutral on the cluster plane's token stream
    # (padded lanes are exact zeros; pinned by test); the sim plane prices
    # the batch's mean effective rank. ``adapter_ranks`` feeds the sim
    # plane's per-adapter ranks (the cluster plane reads them from the
    # pool/store instead).
    rank_aware: bool = True
    adapter_ranks: Optional[Tuple[int, ...]] = None
    # observability (repro.obs): True records per-request spans (queued/
    # prefill/decode + adapter-load, KV-alloc, store-prefetch and, on
    # the cluster plane, the serve.* host scopes on the wall clock) on a
    # TimelineTracer and feeds the metrics registry — export via
    # ServeSystem.observability(). False (default) wires the zero-cost
    # NullTracer: bitwise-identical tokens either way, pinned by test.
    trace: bool = False

    def __post_init__(self):
        # a typo'd plane must fail HERE, not silently price as "host" on
        # the sim plane (cost_model's formula falls through to host for
        # any unknown string) while the cluster plane raises
        if self.transport not in ("host", "fused"):
            raise ValueError(f"unknown transport {self.transport!r} "
                             f"(expected 'host' or 'fused')")
        if self.mesh_shape is not None:
            if self.backend != "cluster":
                raise ValueError(
                    "mesh_shape drives real sharded execution: it needs "
                    "backend='cluster' (the sim plane prices parallelism "
                    "via placement_x instead)")
            if not self.disaggregated:
                raise ValueError(
                    "mesh_shape requires disaggregated=True: the coupled "
                    "step's allgather MoE reassociates floats under a "
                    "mesh, breaking the token bit-identity invariant")
            if len(self.mesh_shape) != 2 or \
                    any(int(d) < 1 for d in self.mesh_shape):
                raise ValueError(
                    f"mesh_shape must be two positive ints (data, model), "
                    f"got {self.mesh_shape!r}")

    # ------------------------- derivations --------------------------- #
    def engine_config(self) -> EngineConfig:
        return EngineConfig(max_len=self.max_len, n_slots=self.max_batch,
                            paged=self.paged, page_size=self.page_size,
                            n_pages=self.n_pages,
                            prefill_chunk=self.prefill_chunk)

    def cluster_config(self) -> ClusterConfig:
        return ClusterConfig(
            n_instances=self.n_instances, n_slots=self.max_batch,
            max_len=self.max_len, disaggregated=self.disaggregated,
            adapter_cache_slots=self.adapter_cache_slots, policy=self.policy,
            step_time=self.step_time, host_bw=self.host_bw,
            layerwise_loading=self.layerwise_loading,
            max_rounds=self.max_rounds, paged=self.paged,
            page_size=self.page_size, n_pages=self.n_pages,
            prefill_chunk=self.prefill_chunk, autoscale=self.autoscale,
            transport=self.transport, hook_launch_us=self.hook_launch_us,
            mesh_shape=self.mesh_shape,
            store_host_bytes=self.store_host_bytes,
            store_dir=self.store_dir, disk_bw=self.disk_bw,
            prefetch=self.prefetch, rank_aware=self.rank_aware)

    def sim_config(self) -> SimConfig:
        return SimConfig(
            n_instances=self.n_instances,
            gpus_per_instance=self.gpus_per_instance,
            max_batch=self.max_batch, duration=self.duration,
            disaggregated=self.disaggregated, server_gpus=self.server_gpus,
            server_cache_slots=self.adapter_cache_slots,
            server_replicas=self.server_replicas,
            placement_x=self.placement_x,
            instance_cache_slots=self.adapter_cache_slots,
            overlap=self.overlap,
            layerwise_loading=self.layerwise_loading,
            fast_kernels=self.fast_kernels,
            slow_kernel_eff_scale=self.slow_kernel_eff_scale,
            protocol=self.protocol,
            policy=self.policy,
            hw=dataclasses.replace(self.hw, disk_bw=self.disk_bw),
            lora_rank=self.lora_rank,
            zipf_s=self.zipf_s, n_adapters=self.n_adapters,
            step_overhead=self.step_overhead, failures=self.failures,
            recoveries=self.recoveries, stragglers=self.stragglers,
            straggler_mitigation=self.straggler_mitigation,
            autoscale=self.autoscale, transport=self.transport,
            hook_launch_us=self.hook_launch_us,
            store_host_bytes=self.store_host_bytes,
            prefetch=self.prefetch,
            adapter_ranks=self.adapter_ranks,
            rank_aware=self.rank_aware)

    # ------------------------ migration shims ------------------------ #
    @classmethod
    def from_sim(cls, sim: SimConfig, **overrides) -> "ServeConfig":
        """Lift a legacy ``SimConfig`` (e.g. the baselines' presets) into
        the front door."""
        slots = sim.server_cache_slots if sim.disaggregated \
            else sim.instance_cache_slots
        kw = dict(
            backend="sim", disaggregated=sim.disaggregated,
            n_instances=sim.n_instances, max_batch=sim.max_batch,
            adapter_cache_slots=slots, policy=sim.policy,
            gpus_per_instance=sim.gpus_per_instance,
            server_gpus=sim.server_gpus,
            server_replicas=sim.server_replicas,
            placement_x=sim.placement_x,
            duration=sim.duration, overlap=sim.overlap,
            layerwise_loading=sim.layerwise_loading,
            fast_kernels=sim.fast_kernels,
            slow_kernel_eff_scale=sim.slow_kernel_eff_scale,
            protocol=sim.protocol,
            hw=sim.hw, lora_rank=sim.lora_rank, zipf_s=sim.zipf_s,
            n_adapters=sim.n_adapters, step_overhead=sim.step_overhead,
            failures=sim.failures, recoveries=sim.recoveries,
            stragglers=sim.stragglers,
            straggler_mitigation=sim.straggler_mitigation,
            autoscale=sim.autoscale, transport=sim.transport,
            hook_launch_us=sim.hook_launch_us,
            store_host_bytes=sim.store_host_bytes,
            disk_bw=sim.hw.disk_bw, prefetch=sim.prefetch,
            adapter_ranks=sim.adapter_ranks, rank_aware=sim.rank_aware)
        kw.update(overrides)
        return cls(**kw)

    @classmethod
    def from_cluster(cls, ccfg: ClusterConfig, **overrides) -> "ServeConfig":
        """Lift a legacy ``ClusterConfig`` into the front door."""
        kw = dict(
            backend="cluster", disaggregated=ccfg.disaggregated,
            n_instances=ccfg.n_instances, max_batch=ccfg.n_slots,
            max_len=ccfg.max_len,
            adapter_cache_slots=ccfg.adapter_cache_slots,
            policy=ccfg.policy, step_time=ccfg.step_time,
            host_bw=ccfg.host_bw, layerwise_loading=ccfg.layerwise_loading,
            max_rounds=ccfg.max_rounds, paged=ccfg.paged,
            page_size=ccfg.page_size, n_pages=ccfg.n_pages,
            prefill_chunk=ccfg.prefill_chunk, autoscale=ccfg.autoscale,
            transport=ccfg.transport, hook_launch_us=ccfg.hook_launch_us,
            mesh_shape=ccfg.mesh_shape,
            store_host_bytes=ccfg.store_host_bytes,
            store_dir=ccfg.store_dir, disk_bw=ccfg.disk_bw,
            prefetch=ccfg.prefetch, rank_aware=ccfg.rank_aware)
        kw.update(overrides)
        return cls(**kw)


# ------------------------------- backends -------------------------------- #
class Backend(Protocol):
    """An execution plane the front door can drive: accepts requests,
    advances virtual time in steps, emits lifecycle ``Event``s, and can
    release an in-flight request."""

    def submit(self, req: Request) -> None: ...

    def cancel(self, rid: int, at: Optional[float] = None) -> List[Event]: ...

    def step(self) -> List[Event]: ...

    def idle(self) -> bool: ...

    @property
    def now(self) -> float: ...

    def requests(self) -> List[Request]: ...

    def kv_stats(self) -> Dict: ...

    def cache_stats(self) -> Dict: ...

    def transport_stats(self) -> Dict: ...

    def default_duration(self) -> float: ...

    def scale_history(self) -> List[Dict]: ...

    def load_adapter(self, adapter_id: int, tensors=None, *,
                     alpha: Optional[float] = None) -> Optional[int]: ...

    def unload_adapter(self, adapter_id: int) -> None: ...

    def close(self) -> None: ...


class SimBackend:
    """The analytic discrete-event plane (wraps ``simulator.Simulation``).

    Token events carry ``token=None``: this plane models *time* (TTFT,
    TPOT, SLO attainment at cluster scale), not token ids."""

    def __init__(self, model: ModelConfig, cfg: ServeConfig, tracer=None):
        self.sim = Simulation(model, cfg.sim_config(), tracer=tracer)
        self._duration = cfg.duration

    def submit(self, req: Request) -> None:
        self.sim.submit(req)

    def cancel(self, rid: int, at: Optional[float] = None) -> List[Event]:
        self.sim.cancel(rid, at=at)
        return []                   # the CANCELLED event arrives via step()

    def step(self) -> List[Event]:
        return [Event(t, rid, kind) for t, rid, kind in self.sim.step()]

    def idle(self) -> bool:
        return self.sim.idle()

    @property
    def now(self) -> float:
        return self.sim.now

    def requests(self) -> List[Request]:
        return list(self.sim.requests)

    def kv_stats(self) -> Dict:
        return {}                   # the analytic plane holds no real KV

    def cache_stats(self) -> Dict:
        return {"caches": {k: c.stats() for k, c in self.sim.caches.items()},
                "store": self.sim.store.stats() if self.sim.store else {}}

    def transport_stats(self) -> Dict:
        return self.sim.transport_stats()   # modeled launch counts

    def default_duration(self) -> float:
        return self._duration

    def scale_history(self) -> List[Dict]:
        sc = self.sim._scaler
        return list(sc.history) if sc is not None else []

    def load_adapter(self, adapter_id: int, tensors=None, *,
                     alpha: Optional[float] = None) -> Optional[int]:
        # the analytic plane has no tensors to validate — only the id joins
        self.sim.load_adapter(adapter_id)
        return None

    def unload_adapter(self, adapter_id: int) -> None:
        self.sim.unload_adapter(adapter_id)

    def close(self) -> None:
        pass                        # nothing real to tear down


class ClusterBackend:
    """The real JAX plane (wraps the slot-engine ``Cluster`` session):
    actual decode steps, real token ids, paged or dense KV."""

    def __init__(self, model: ModelConfig, params, cfg: ServeConfig, pool,
                 server=None, server_pool=None, tracer=None):
        self.cluster = Cluster(model, params, cfg.cluster_config(), pool,
                               server_pool=server_pool, server=server,
                               tracer=tracer)
        self.cluster.open()
        self.max_rounds = cfg.max_rounds
        self.step_time = cfg.step_time
        self._reqs: List[Request] = []
        self._req_by_rid: Dict[int, Request] = {}
        self._cancels: List[Tuple[float, int]] = []   # (at, rid) scheduled

    def submit(self, req: Request) -> None:
        self.cluster.submit(req)    # raises ValueError -> REJECTED
        self._reqs.append(req)
        self._req_by_rid[req.rid] = req

    def _live_cancels(self) -> List[Tuple[float, int]]:
        """Scheduled cancels whose target is still in flight — a cancel
        outliving its (finished or already-cancelled) request must not keep
        the backend awake spinning empty rounds toward max_rounds."""
        return [(t, rid) for t, rid in self._cancels
                if (r := self._req_by_rid.get(rid)) is not None
                and r.finish < 0 and not r.cancelled]

    def cancel(self, rid: int, at: Optional[float] = None) -> List[Event]:
        now = self.cluster.now
        if at is not None and at > now:
            self._cancels.append((at, rid))
            return []
        if self.cluster.cancel(rid):
            wall = wall_time() if self.cluster.tracer.enabled else None
            return [Event(now, rid, "cancelled", wall=wall)]
        return []

    def step(self) -> List[Event]:
        if self.cluster.rnd >= self.max_rounds:
            raise RuntimeError(
                f"cluster exceeded max_rounds={self.max_rounds} with "
                f"unfinished work — adapter cache too small?")
        evs: List[Event] = []
        now = self.cluster.now
        self._cancels = self._live_cancels()
        due = [(t, rid) for t, rid in self._cancels if t <= now]
        self._cancels = [(t, rid) for t, rid in self._cancels if t > now]
        for t, rid in due:
            evs.extend(self.cancel(rid))
        rep = self.cluster.step_round()
        # wall stamps (tracing only): where in the round each event's
        # moment fell — control, enqueue, the request's own prefill, and
        # the end of the round's decode steps
        w = rep.get("wall") or {}
        w_admit = w.get("admit", {})
        w_end = w.get("end")
        evs.extend(Event(rep["now"], -1, f"scale:{a.kind}", detail=a.reason,
                         wall=w.get("control")) for a in rep["scale"])
        evs.extend(Event(rep["now"], r.rid, "queued", wall=w.get("enqueue"))
                   for r in rep["enqueued"])
        evs.extend(Event(rep["now"], r.rid, "prefill",
                         wall=w_admit.get(r.rid)) for r in rep["admitted"])
        evs.extend(Event(rep["step_end"], rid, "token", token=tok,
                         wall=w_end) for rid, tok in rep["tokens"].items())
        evs.extend(Event(rep["step_end"], r.rid, "finished", wall=w_end)
                   for r in rep["finished"])
        return evs

    def idle(self) -> bool:
        return self.cluster.idle() and not self._live_cancels()

    @property
    def now(self) -> float:
        return self.cluster.now

    def requests(self) -> List[Request]:
        return list(self._reqs)

    def kv_stats(self) -> Dict:
        return self.cluster.kv_stats()

    def cache_stats(self) -> Dict:
        return self.cluster.cache_stats()

    def transport_stats(self) -> Dict:
        return self.cluster.transport_stats()   # measured launch counts

    def default_duration(self) -> float:
        return max(self.cluster.rnd, 1) * self.step_time

    def scale_history(self) -> List[Dict]:
        return self.cluster.scale_history()

    def load_adapter(self, adapter_id: int, tensors=None, *,
                     alpha: Optional[float] = None) -> Optional[int]:
        if tensors is None:
            raise ValueError(
                "the cluster plane loads REAL weights: pass tensors= in "
                "the canonical host format ({'<target>.A'/'<target>.B'})")
        return self.cluster.load_adapter(adapter_id, tensors, alpha=alpha)

    def unload_adapter(self, adapter_id: int) -> None:
        self.cluster.unload_adapter(adapter_id)

    def close(self) -> None:
        self.cluster.close()


# ---------------------------- request handle ----------------------------- #
class RequestHandle:
    """Client-side view of one submitted request: live state, the token
    stream so far, per-token callbacks, an iterator that pumps the system,
    and ``cancel()``."""

    def __init__(self, system: "ServeSystem", request: Request,
                 slo_class: SLOClass):
        self._system = system
        self.request = request
        self.rid = request.rid
        self.slo_class = slo_class
        self.state = RequestState.QUEUED
        self.tokens: List[int] = []          # real ids (cluster plane)
        self.n_tokens = 0                    # lifecycle count (both planes)
        self.events: List[Event] = []
        self.error: Optional[str] = None
        self._stream: List[Optional[int]] = []
        self._cbs: List[Callable[["RequestHandle", Optional[int]], None]] = []

    # ------------------------- consumption --------------------------- #
    @property
    def done(self) -> bool:
        return self.state in TERMINAL_STATES

    def on_token(self, cb: Callable[["RequestHandle", Optional[int]], None]
                 ) -> "RequestHandle":
        """Register a per-token callback ``cb(handle, token)``; fires the
        round each token is decoded."""
        self._cbs.append(cb)
        return self

    def result(self) -> List[int]:
        """Pump the system until this request is terminal (or the backend
        runs dry); returns the tokens decoded so far."""
        while not self.done and not self._system.backend.idle():
            self._system.step()
        return self.tokens

    def __iter__(self) -> Iterator[Optional[int]]:
        """Stream tokens as they are decoded, pumping the system between
        yields — mid-stream consumption while OTHER requests keep being
        admitted/evicted around this one."""
        sent = 0
        while True:
            while sent < len(self._stream):
                yield self._stream[sent]
                sent += 1
            if self.done or self._system.backend.idle():
                return
            self._system.step()

    def cancel(self, at: Optional[float] = None) -> bool:
        """Cancel this request (now, or at virtual time ``at``). Frees its
        decode slot, KV pages, and adapter pin at the next round/event
        boundary; it will never count as finished."""
        if self.done:
            return False
        return self._system.cancel(self.rid, at=at)

    # --------------------- metrics passthrough ----------------------- #
    @property
    def ttft(self) -> float:
        return self.request.ttft

    @property
    def tpot(self) -> float:
        return self.request.tpot

    def __repr__(self):
        return (f"RequestHandle(rid={self.rid}, state={self.state.name}, "
                f"tokens={self.n_tokens}/{self.request.output_len})")

    # -------------------------- internals ----------------------------- #
    def _reject(self, reason: str) -> None:
        self.state = RequestState.REJECTED
        self.error = reason

    def _apply(self, ev: Event) -> None:
        self.events.append(ev)
        if ev.kind == "queued":
            if self.state == RequestState.QUEUED:
                return               # submit() already set it
            self.state = RequestState.QUEUED   # requeued after a failure
        elif ev.kind == "prefill":
            self.state = RequestState.PREFILLING
        elif ev.kind == "token":
            self.state = RequestState.DECODING
            self.n_tokens += 1
            self._stream.append(ev.token)
            if ev.token is not None:
                self.tokens.append(ev.token)
            for cb in self._cbs:
                cb(self, ev.token)
        elif ev.kind == "finished":
            self.state = RequestState.FINISHED
        elif ev.kind == "cancelled":
            self.state = RequestState.CANCELLED


# ------------------------------ the system ------------------------------- #
class ServeSystem:
    """The front door: one object that owns a backend, assigns rids, fans
    lifecycle events out to handles, and summarizes SLO metrics."""

    def __init__(self, cfg: ServeConfig, model: ModelConfig, params=None,
                 pool=None, server=None, server_pool=None):
        self.cfg = cfg
        self.model = model
        # observability plane: one tracer threads through the backend
        # (cluster/sim, caches, engines) and one hub folds the lifecycle
        # event stream into request-stage spans + the metrics registry.
        # trace=False wires the zero-cost NULL_TRACER and the hub is
        # never driven.
        # The cluster plane stamps the wall clock: its scope spans enter
        # jax.profiler.TraceAnnotation (so they land on a profile's host
        # plane) and Python garbage collections become serve.gc spans.
        # The sim plane keeps its virtual clock.
        real = cfg.backend == "cluster"
        if cfg.trace:
            self.tracer = TimelineTracer(
                annotate=jax.profiler.TraceAnnotation if real else None,
                gc_spans=real)
        else:
            self.tracer = NULL_TRACER
        self._clock = wall_time if real else None
        self._hub = ObservabilityHub(self.tracer)
        if cfg.backend == "sim":
            self.backend: Backend = SimBackend(model, cfg,
                                               tracer=self.tracer)
        elif cfg.backend == "cluster":
            if params is None or pool is None:
                raise ValueError(
                    "backend='cluster' runs the real model: pass params= "
                    "and pool= (or use backend='sim' for the analytic "
                    "plane)")
            if cfg.disaggregated and server is None and server_pool is None:
                server_pool = self._make_server_pool(model, cfg, pool)
            self.backend = ClusterBackend(model, params, cfg, pool,
                                          server=server,
                                          server_pool=server_pool,
                                          tracer=self.tracer)
        else:
            raise ValueError(f"unknown backend {cfg.backend!r} "
                             f"(expected 'sim' or 'cluster')")
        self.handles: Dict[int, RequestHandle] = {}
        # DEPRECATED shim: scale:* events also land here, as before.
        # They are now first-class trace events (instants on the
        # "control" track) — prefer observability().tracer / registry.
        self.scale_events: List[Event] = []
        self._rid = itertools.count()

    @staticmethod
    def _make_server_pool(model: ModelConfig, cfg: ServeConfig, pool):
        """Default elastic pool of single-device LoRA-Server replicas.
        Replica slot tables are sized so the autoscaler's cache-resize
        ceiling always physically fits. Under a mesh the slot tables are
        PARTITIONED: each replica holds its affinity share of the cache
        instead of a full duplicate."""
        slots = cfg.adapter_cache_slots
        if cfg.autoscale is not None:
            slots = max(slots, min(cfg.autoscale.max_cache_slots, pool.n))
        return ServerPool.build(model, pool, cache_slots=slots,
                                n_replicas=max(cfg.server_replicas, 1),
                                partition_slots=cfg.mesh_shape is not None)

    # --------------------------- submission -------------------------- #
    def submit(self, prompt: Optional[Sequence[int]] = None,
               adapter_id: int = 0, *, max_new_tokens: int = 8,
               prompt_len: Optional[int] = None,
               arrival: Optional[float] = None,
               slo_class: SLOClass = INTERACTIVE,
               on_token: Optional[Callable] = None,
               rid: Optional[int] = None) -> RequestHandle:
        """Submit one request; returns its handle immediately (state QUEUED,
        or REJECTED if it violates the admission contract — never raises
        for a bad request). ``prompt`` is real token ids (cluster plane);
        without one, ``prompt_len`` synthesizes a deterministic prompt from
        the rid."""
        if prompt is None and prompt_len is None:
            raise TypeError("submit() needs prompt= or prompt_len=")
        rid = next(self._rid) if rid is None else rid
        # materialize first: `if prompt` would crash on numpy/jnp arrays
        # (ambiguous truth value) and silently drop an explicit empty prompt
        ids = tuple(int(t) for t in prompt) if prompt is not None else ()
        plen = len(ids) if prompt is not None else int(prompt_len)
        req = Request(rid, int(adapter_id),
                      arrival=self.backend.now if arrival is None
                      else float(arrival),
                      prompt_len=plen, output_len=int(max_new_tokens),
                      prompt=ids)
        handle = RequestHandle(self, req, slo_class)
        if on_token is not None:
            handle.on_token(on_token)
        if prompt is not None and plen == 0:
            handle._reject(f"request {rid}: empty prompt")
            return handle
        try:
            self.backend.submit(req)
        except ValueError as e:       # admission contract violation
            handle._reject(str(e))
            return handle
        self.handles[rid] = handle
        return handle

    def submit_workload(self, requests: Sequence[Request],
                        slo_class: SLOClass = INTERACTIVE
                        ) -> List[RequestHandle]:
        """Replay a generated workload (``workload.generate``) through the
        front door, preserving each request's rid and arrival time."""
        handles = [self.submit(adapter_id=r.adapter_id,
                               prompt=r.prompt or None,
                               prompt_len=r.prompt_len,
                               max_new_tokens=r.output_len,
                               arrival=r.arrival, rid=r.rid,
                               slo_class=slo_class)
                   for r in requests]
        # keep auto-rids collision-free without ever rewinding the counter
        # below rids already issued by plain submit() calls
        top = max((r.rid for r in requests), default=-1)
        self._rid = itertools.count(max(top + 1, next(self._rid)))
        return handles

    # ---------------------------- pumping ----------------------------- #
    def step(self) -> List[Event]:
        """Advance the backend one quantum; route events to handles.
        With tracing on, every event also feeds the observability hub
        (request-stage spans + metrics). Scaling events (rid=-1) become
        trace instants AND still accumulate on the deprecated
        ``scale_events`` shim."""
        evs = self.backend.step()
        traced = self.tracer.enabled
        with self.tracer.scope("serve.events"):
            for ev in evs:
                if traced:
                    self._hub.on_event(ev)
                if ev.kind.startswith("scale"):
                    self.scale_events.append(ev)
                    continue
                h = self.handles.get(ev.rid)
                if h is not None:
                    h._apply(ev)
        return evs

    def drain(self) -> None:
        """Run until the backend is idle (every request terminal or the
        plane's horizon reached)."""
        while not self.backend.idle():
            self.step()

    def cancel(self, rid: int, at: Optional[float] = None) -> bool:
        h = self.handles.get(rid)
        if h is None or h.done:
            return False
        for ev in self.backend.cancel(rid, at=at):
            self.handles[ev.rid]._apply(ev)
        return True

    @property
    def now(self) -> float:
        return self.backend.now

    # ----------------------- adapter lifecycle ------------------------ #
    def load_adapter(self, adapter_id: int, tensors=None, *,
                     alpha: Optional[float] = None) -> Optional[int]:
        """Register a new adapter mid-run (vLLM-style dynamic load): the
        id becomes targetable by subsequent ``submit`` calls. On the
        cluster plane ``tensors`` is the canonical host format
        ({"<target>.A"/"<target>.B"} at the adapter's true rank) and is
        validated against the model config; ``alpha`` rescales from the
        raw alpha/r convention into the pool's uniform scale; the
        adapter's rank is returned. The sim plane registers the id alone
        (returns None). Disaggregated only; raises ValueError on a
        coupled system or invalid tensors."""
        return self.backend.load_adapter(adapter_id, tensors, alpha=alpha)

    def unload_adapter(self, adapter_id: int) -> None:
        """Remove an adapter from every store tier and the device cache.
        Refused (ValueError) while any unfinished request references it —
        cancel or drain those first."""
        self.backend.unload_adapter(adapter_id)

    def close(self) -> None:
        """Tear down backend resources (the adapter store's prefetch
        thread and owned disk-tier tempdir) and stop the tracer's
        garbage-collection hook. Idempotent."""
        self.backend.close()
        self.tracer.close()

    # ---------------------------- metrics ----------------------------- #
    def kv_stats(self) -> Dict:
        return self.backend.kv_stats()

    def cache_stats(self) -> Dict:
        """Adapter-plane telemetry: per-cache device-tier counters
        (hits/misses/evictions/prefetch_hits/miss_load_seconds under
        "caches") and the store's host/disk tier counters (under
        "store"). Benches read THIS instead of hand-instrumenting."""
        return self.backend.cache_stats()

    def transport_stats(self) -> Dict:
        """Hook-transport launch accounting (host dispatches, device
        programs, LUT uploads, per-step rate): measured on the cluster
        plane, modeled on the sim plane, empty in coupled mode. Benches
        and tests read THIS instead of hand-instrumenting dispatch
        counters."""
        return self.backend.transport_stats()

    def scale_history(self) -> List[Dict]:
        """Autoscaler control-tick record (rate, LB, targets, actions) —
        what the provisioning benchmarks plot; empty when static."""
        return self.backend.scale_history()

    def summary(self, duration: Optional[float] = None,
                slo_class: Optional[SLOClass] = None,
                warmup: float = 0.1) -> Summary:
        """SLO summary over the live request objects — identical math for
        both planes. ``slo_class`` filters to that class's requests and
        applies its thresholds; default: all requests, paper SLOs."""
        reqs = self.backend.requests()
        if slo_class is not None:
            keep = {h.rid for h in self.handles.values()
                    if h.slo_class.name == slo_class.name}
            reqs = [r for r in reqs if r.rid in keep]
        sc = slo_class or INTERACTIVE
        s = metrics.summarize(
            reqs, duration if duration is not None
            else self.backend.default_duration(),
            ttft_slo=sc.ttft_slo, tpot_slo=sc.tpot_slo, warmup=warmup,
            cache_stats=self.backend.cache_stats(),
            transport_stats=self.backend.transport_stats())
        if self.tracer.enabled:
            # Summary is rebuilt on top of the registry view: every
            # numeric field mirrors into a summary_<field> gauge
            self._hub.publish_summary(s)
        return s

    def observability(self) -> Observability:
        """The observability facade: tracer + metrics registry + the
        Perfetto/Prometheus/JSONL exporters. Always available — with
        ``trace=False`` the tracer is the NullTracer and only the
        pull-refreshed registry carries data."""
        return Observability(self._hub, self.backend, clock=self._clock)


def build_system(cfg: ServeConfig, model: ModelConfig, *, params=None,
                 pool=None, server=None, server_pool=None) -> ServeSystem:
    """Build the one serving front door for any plane combination:
    coupled/disaggregated x sim/cluster x dense/paged KV x static/elastic.
    ``server=`` (single LoRAServer) remains as a migration shim; new code
    passes ``server_pool=`` (or lets the system build one)."""
    return ServeSystem(cfg, model, params=params, pool=pool, server=server,
                       server_pool=server_pool)
