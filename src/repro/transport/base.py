"""Transport plane contract + shared jit helpers (paper §5 "GPU-initiated
communication").

A *transport* is the piece of the disaggregated data path that moves the
per-layer LoRA hook work between the LLM instance and the LoRA-Server pool
during one continuous-batching decode step. Two planes implement it:

  HostTransport   (transport/host.py)  : the host-mediated baseline — every
                  MoE layer makes two host round-trips to the server pool
                  (2 x n_layers jitted hook dispatches per decode step, plus
                  per-replica launches), so the step runs eagerly and the
                  CPU launch tail is on the critical path. Instrumented so
                  that cost is measurable, not just asserted.
  FusedTransport  (transport/fused.py) : the GPU-initiated plane — the
                  adapter->slot LUT and replica-affinity routing live in
                  device-resident arrays (re-uploaded only when residency
                  changes, never per token), so the WHOLE decode step —
                  attention, base MoE GEMMs, and both LoRA hooks across all
                  layers and replicas — compiles into ONE jitted program
                  per shape bucket: O(1) host dispatches per token.

Both planes return token ids (not logits): the transport owns everything
between "engine hands over the batch" and "tokens come back", which is
exactly the region whose dispatch count differs.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Protocol

import jax
import jax.numpy as jnp
import numpy as np


def kv_donating_jit(fn, kv_argnums, **jit_kw):
    """jit ``fn`` donating the KV buffers at ``kv_argnums`` so XLA updates
    them in place (avoiding a 2x KV peak per decoded token). CPU does not
    implement donation (it would just warn), so the backend is probed
    LAZILY on first call — probing at import would initialize the JAX
    backend as a side effect, breaking later platform overrides."""
    jitted = []

    def call(*args):
        if not jitted:
            kw = dict(jit_kw)
            if jax.default_backend() != "cpu":
                kw["donate_argnums"] = kv_argnums
            jitted.append(jax.jit(fn, **kw))
        return jitted[0](*args)
    return call


@jax.jit  # cache must survive this call: NOT donated
def gather_rows(k, v, sel):
    return jnp.take(k, sel, axis=1), jnp.take(v, sel, axis=1)


def _scatter_rows_fn(k, v, k_rows, v_rows, idx):
    return (k.at[:, idx].set(k_rows, mode="drop"),
            v.at[:, idx].set(v_rows, mode="drop"))


scatter_rows = kv_donating_jit(_scatter_rows_fn, (0, 1))


@dataclasses.dataclass
class TransportStats:
    """Launch accounting for one transport (shared by every engine of a
    cluster so the counts are per-SYSTEM, matching what a profiler would
    see on the host). ``host_dispatches`` counts jitted program launches
    initiated from Python on the decode path; ``lut_uploads`` counts
    residency-change uploads (host->device copies OFF the per-token path);
    ``hook_dispatches`` isolates the LoRA-hook share of the launches."""
    transport: str = "host"
    steps: int = 0                  # decode steps served
    host_dispatches: int = 0        # host-initiated launches on decode path
    hook_dispatches: int = 0        # the 2 x n_layers server-hook share
    lut_uploads: int = 0            # residency/LUT device refreshes
    # effective-rank telemetry: the per-row rank the hook compute PAID
    # (true slot rank when rank-aware, the padded pool rank otherwise),
    # accumulated over every active row of every decode step
    pool_rank: int = 0              # padded slot-pool rank (the baseline)
    active_rank_rows: int = 0       # active rows observed
    active_rank_sum: int = 0        # summed paid rank over those rows
    max_active_rank: int = 0

    @property
    def device_programs(self) -> int:
        """Device programs run on the decode path — identical to the host
        dispatch count on this backend (no device-initiated chaining), so
        it is derived, not a second counter to keep in sync."""
        return self.host_dispatches

    def per_step(self) -> float:
        return self.host_dispatches / max(self.steps, 1)

    def mean_active_rank(self) -> float:
        return self.active_rank_sum / self.active_rank_rows \
            if self.active_rank_rows else 0.0

    def rank_flop_savings(self) -> float:
        """Fraction of the padded hook FLOPs the rank bound eliminated:
        1 - mean_paid_rank / pool_rank (0 when nothing observed)."""
        if not (self.pool_rank and self.active_rank_rows):
            return 0.0
        return 1.0 - self.mean_active_rank() / self.pool_rank

    def observe_ranks(self, server, adapter_ids) -> None:
        """Bill one step's active rows at the rank the hook compute pays:
        the slot's TRUE rank when ``server`` is rank-aware, else its padded
        pool rank. Works against a ``ServerPool`` or a bare
        ``LoRAServer`` (both expose ``true_rank``/``pool_rank``)."""
        ids = np.asarray(adapter_ids)
        active = ids[ids >= 0]
        if active.size == 0:
            return
        pool_rank = int(getattr(server, "pool_rank", 0) or
                        getattr(server, "r", 0))
        tr = getattr(server, "true_rank", None)
        if tr is not None and getattr(server, "rank_aware", True):
            ranks = np.array([tr(int(a)) for a in active])
            ranks = np.where(ranks > 0, ranks, pool_rank)
        else:
            ranks = np.full(active.size, pool_rank)
        self.active_rank_rows += int(active.size)
        self.active_rank_sum += int(ranks.sum())
        self.max_active_rank = max(self.max_active_rank, int(ranks.max()))
        self.pool_rank = max(self.pool_rank, pool_rank)

    def as_dict(self) -> Dict[str, float]:
        return {
            "transport": self.transport,
            "steps": self.steps,
            "host_dispatches": self.host_dispatches,
            "device_programs": self.device_programs,
            "hook_dispatches": self.hook_dispatches,
            "lut_uploads": self.lut_uploads,
            "host_dispatches_per_step": round(self.per_step(), 3),
            "mean_active_rank": round(self.mean_active_rank(), 3),
            "max_active_rank": self.max_active_rank,
            "rank_flop_savings": round(self.rank_flop_savings(), 4),
        }


class Transport(Protocol):
    """One disaggregated decode step: batch in, token ids + updated KV out.

    ``sel``/``scatter_idx`` drive the dense-slab gather/scatter (ignored
    when ``block_table`` selects the paged layout, where rows read and
    write the shared pool directly)."""

    stats: TransportStats

    def refresh(self) -> bool:
        """Install residency changes on the device (True on upload)."""

    def decode_step(self, params, cfg, k, v, toks, pos_vec, adapter_ids,
                    lora_scale, *, sel=None, scatter_idx=None,
                    block_table=None):
        """(token ids on the device, k, v): the caller pulls the ids to
        the host, which is where it waits for the step."""


def make_transport(name: str, server, n_adapters: Optional[int] = None,
                   mesh_ctx=None, tracer=None) -> Transport:
    """Build the named transport plane over ``server`` (a ``ServerPool``
    or a legacy single ``LoRAServer``). ``mesh_ctx`` (an
    ``ExpertParallelCtx``) runs the base expert GEMMs of either plane
    expert-parallel over its mesh; ``tracer`` (a ``repro.obs`` tracer)
    takes the step's ``serve.*`` scopes."""
    from repro.transport.fused import FusedTransport
    from repro.transport.host import HostTransport
    if name == "host":
        return HostTransport(server, mesh_ctx=mesh_ctx, tracer=tracer)
    if name == "fused":
        return FusedTransport(server, n_adapters=n_adapters,
                              mesh_ctx=mesh_ctx, tracer=tracer)
    raise ValueError(f"unknown transport {name!r} "
                     f"(expected 'host' or 'fused')")
