"""Host-mediated transport: today's per-hook dispatch path, instrumented.

This plane is exactly the pre-transport behavior of ``Engine.step`` in
disaggregated mode — the decode step runs eagerly on the host because every
MoE layer's two hook points call back into Python (``ServerPool.compute``
-> per-replica jitted server steps). What the refactor adds is *launch
accounting*: every jitted program this transport starts from the host on
the decode path is counted, so the O(L x replicas) per-token launch tail
(2L hook calls, one launch per engaged replica, plus gather/scatter/select
overhead) the paper (and CaraServe's CPU-mediation critique) attributes to
host-driven LoRA coordination becomes a measured baseline rather than
folklore. ``FusedTransport`` is the O(1) alternative.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core import disagg as disagg_mod
from repro.obs.trace import NULL_TRACER
from repro.transport.base import TransportStats, gather_rows, scatter_rows


class _CountingServer:
    """Delegating proxy that bills each hook call's device launches to the
    transport's stats. ``ServerPool`` reports real per-replica launches via
    its ``replica_launches`` counter; a bare ``LoRAServer`` is one launch
    per hook call."""

    def __init__(self, server, stats: TransportStats):
        self._server = server
        self._stats = stats

    def compute(self, hook, layer, rows, adapter_ids, expert_ids):
        before = getattr(self._server, "replica_launches", None)
        # host-mediated hop: activations come back to the host before the
        # server-side jits see them. This is the honest data path of this
        # plane, and it also keeps the per-replica server programs on their
        # own (single-device) assignment when the client math runs on a
        # mesh — mesh-committed rows would otherwise poison the server jit.
        out = self._server.compute(hook, layer, np.asarray(rows),
                                   np.asarray(adapter_ids),
                                   np.asarray(expert_ids))
        launches = 1 if before is None else \
            max(self._server.replica_launches - before, 1)
        self._stats.hook_dispatches += 1
        self._stats.host_dispatches += launches
        return out


class HostTransport:
    """Per-hook host dispatch (the measurable baseline plane)."""

    name = "host"

    def __init__(self, server, mesh_ctx=None, tracer=None):
        self.server = server
        self.mesh_ctx = mesh_ctx
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.stats = TransportStats(transport="host")
        self._counting = _CountingServer(server, self.stats)

    def refresh(self) -> bool:
        return False            # residency lives on the host: no upload

    def decode_step(self, params, cfg, k, v, toks, pos_vec, adapter_ids,
                    lora_scale, *, sel=None, scatter_idx=None,
                    block_table=None):
        st = self.stats
        st.steps += 1
        st.observe_ranks(self.server, adapter_ids)
        # the eager step IS the dispatch: every hook returns to the host
        with self.tracer.scope("serve.engine.dispatch"):
            return self._step(params, cfg, k, v, toks, pos_vec,
                              adapter_ids, lora_scale, sel, scatter_idx,
                              block_table)

    def _step(self, params, cfg, k, v, toks, pos_vec, adapter_ids,
              lora_scale, sel, scatter_idx, block_table):
        st = self.stats
        if block_table is not None:
            logits, k, v = disagg_mod.disagg_decode_step_slots(
                params, cfg, k, v, toks, pos_vec, self._counting,
                adapter_ids, lora_scale, block_table=block_table,
                mesh_ctx=self.mesh_ctx)
            st.host_dispatches += 1          # token-select launch
        else:
            k_rows, v_rows = gather_rows(k, v, sel)
            logits, k_rows, v_rows = disagg_mod.disagg_decode_step_slots(
                params, cfg, k_rows, v_rows, toks, pos_vec, self._counting,
                adapter_ids, lora_scale, mesh_ctx=self.mesh_ctx)
            k, v = scatter_rows(k, v, k_rows, v_rows, scatter_idx)
            st.host_dispatches += 3          # gather + scatter + select
        logits = logits[:, : cfg.vocab_size]
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return tok, k, v
