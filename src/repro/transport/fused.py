"""GPU-initiated transport: the disaggregated decode step as ONE program.

The host plane re-crosses the Python boundary 2 x n_layers times per token
because adapter->slot resolution (``LoRAServer.resolve_slots``, host numpy)
and replica-affinity routing (``ServerPool.compute``'s per-replica masking)
live on the host. This plane moves both INTO the device:

  DeviceLoraView : a pytree of device-resident arrays — the replica slot
                   pools stacked on a leading replica axis plus one
                   adapter->slot LUT (slot on the adapter's affinity home,
                   -1 = not resident). Its ``compute`` is pure jnp, so it
                   satisfies the ``LoRAServer.compute`` contract *under a
                   jit trace*: ``disagg_decode_step_slots`` runs unchanged,
                   which is what guarantees the hook math (and therefore
                   the token stream) cannot diverge from the host plane.
  FusedTransport : compiles the ENTIRE decode step — attention, base MoE
                   GEMMs, both LoRA hooks across all layers and replicas,
                   KV gather/scatter, and token selection — into one jitted
                   program per shape bucket: O(1) host dispatches per step.

The view is re-uploaded ONLY when the server pool's residency actually
changed (``LoRACache.drain_dirty`` -> ``ServerPool.sync`` bumps the
mutation counters this transport fingerprints), never on the decode path:
on real hardware this is the control-plane DMA that installs a new adapter,
while every token's routing decisions are device-side gathers — the
paper's "GPU-initiated communication".
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import disagg as disagg_mod
from repro.obs.trace import NULL_TRACER
from repro.transport.base import TransportStats, kv_donating_jit

F32 = jnp.float32


# The TPU compiler splits a gather whose slices are larger than this, and
# does so by first copying the whole operand into column blocks: for a slot
# pool, a copy of the pool on every step (Mixtral's 0.94 GB up_B pool).
_GATHER_SLICE_BYTES = 1 << 19


def _pool_blocks(pool, flat):
    """The (d_in, r) or (r, d_out) blocks of a (R, L, M, E, ...) slot pool
    at flat indices ``flat`` (T,), read from the pool as it is stored: one
    gather where a block fits one gather slice, else one dynamic slice per
    row (either way no copy of the pool)."""
    blocks = pool.reshape(-1, *pool.shape[4:])
    if blocks[0].size * blocks.dtype.itemsize <= _GATHER_SLICE_BYTES:
        return blocks[flat]
    return jnp.stack([jax.lax.dynamic_index_in_dim(blocks, flat[t], 0, False)
                      for t in range(flat.shape[0])])


@jax.tree_util.register_pytree_node_class
class DeviceLoraView:
    """Device-resident LoRA routing state: stacked replica slot pools
    (R, L, M, E, d_in, r) per hook factor + the adapter->slot LUT.

    ``compute`` is the traced twin of ``LoRAServer.compute``'s flat path:
    the same per-row blocks (read in place, ``_pool_blocks``) and the same
    f32 einsum contraction per row, with the affinity home ``aid % R``
    replacing the host-side replica masking (each row reads exactly the
    array its home replica holds, and inactive rows are exact 0.0 —
    bit-compatible with the host plane's masked sum)."""

    def __init__(self, up_A, up_B, down_A, down_B, slot_lut, slot_ranks):
        self.up_A, self.up_B = up_A, up_B
        self.down_A, self.down_B = down_A, down_B
        self.slot_lut = slot_lut
        self.slot_ranks = slot_ranks            # (R, M) true rank per slot

    def tree_flatten(self):
        return ((self.up_A, self.up_B, self.down_A, self.down_B,
                 self.slot_lut, self.slot_ranks), None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    def compute(self, hook, layer, rows, adapter_ids, expert_ids):
        A, B = (self.up_A, self.up_B) if hook == "up" else \
            (self.down_A, self.down_B)
        R = A.shape[0]
        ids = jnp.asarray(adapter_ids)
        n = self.slot_lut.shape[0]
        slots = jnp.where((ids >= 0) & (ids < n),
                          self.slot_lut[jnp.clip(ids, 0, n - 1)], -1)
        homes = jnp.where(slots >= 0, jnp.maximum(ids, 0) % R, 0)
        ss = jnp.maximum(slots, 0)
        eids = jnp.asarray(expert_ids, jnp.int32)
        _, L, M, E = A.shape[:4]
        flat = ((homes * L + layer) * M + ss) * E + eids
        a = _pool_blocks(A, flat)               # (T, d_in, r)
        b = _pool_blocks(B, flat)               # (T, r, d_out)
        h = jnp.einsum("td,tdr->tr", rows.astype(F32), a.astype(F32))
        # rank bound: past-rank lanes of h hold exact 0.0 already (the pool
        # zero-pads them), so trimming them is bitwise-neutral. The "up"
        # hook's r axis is block-diagonal over the fused gate/up pair, so
        # the true rank repeats per r_pool-wide block — hence the modulus.
        r_pool = self.down_A.shape[-1]
        rank = self.slot_ranks[homes, ss]       # (T,) paid rank per row
        col = jax.lax.broadcasted_iota(jnp.int32, h.shape, 1)
        h = jnp.where((col % r_pool) < rank[:, None], h, 0.0)
        out = jnp.einsum("tr,tro->to", h, b.astype(F32))
        return jnp.where((slots >= 0)[:, None], out, 0.0)


# ------------------------------------------------------------------ #
# the fused step: one compiled program per shape bucket               #
# ------------------------------------------------------------------ #
def _fused_dense_fn(params, cfg, k, v, sel, scatter_idx, toks, pos_vec,
                    view, ads, scale, mesh_ctx=None):
    k_rows, v_rows = jnp.take(k, sel, axis=1), jnp.take(v, sel, axis=1)
    logits, k_rows, v_rows = disagg_mod.disagg_decode_step_slots(
        params, cfg, k_rows, v_rows, toks, pos_vec, view, ads, scale,
        mesh_ctx=mesh_ctx)
    with jax.named_scope("lm_head"):
        tok = jnp.argmax(logits[:, : cfg.vocab_size], -1).astype(jnp.int32)
    k = k.at[:, scatter_idx].set(k_rows, mode="drop")
    v = v.at[:, scatter_idx].set(v_rows, mode="drop")
    return tok, k, v


_fused_dense = kv_donating_jit(_fused_dense_fn, (2, 3),
                               static_argnames=("cfg",))


def _fused_paged_fn(params, cfg, k_pool, v_pool, bt, toks, pos_vec, view,
                    ads, scale, mesh_ctx=None):
    logits, k_pool, v_pool = disagg_mod.disagg_decode_step_slots(
        params, cfg, k_pool, v_pool, toks, pos_vec, view, ads, scale,
        block_table=bt, mesh_ctx=mesh_ctx)
    with jax.named_scope("lm_head"):
        tok = jnp.argmax(logits[:, : cfg.vocab_size], -1).astype(jnp.int32)
    return tok, k_pool, v_pool


_fused_paged = kv_donating_jit(_fused_paged_fn, (2, 3),
                               static_argnames=("cfg",))


def _make_fused_steps(mesh_ctx):
    """Instance-local jitted step pair for a mesh-bearing transport.

    The mesh ctx must be CLOSED OVER, not passed as a jit argument (it is
    not a pytree), and the module-level jits above must never trace with a
    mesh baked in — a closure pair per transport keeps the ctx-free cache
    clean while the fused step still compiles to one program whose expert
    GEMMs are shard_map-partitioned over the mesh."""
    def dense(params, cfg, k, v, sel, scatter_idx, toks, pos_vec, view,
              ads, scale):
        return _fused_dense_fn(params, cfg, k, v, sel, scatter_idx, toks,
                               pos_vec, view, ads, scale,
                               mesh_ctx=mesh_ctx)

    def paged(params, cfg, k_pool, v_pool, bt, toks, pos_vec, view, ads,
              scale):
        return _fused_paged_fn(params, cfg, k_pool, v_pool, bt, toks,
                               pos_vec, view, ads, scale,
                               mesh_ctx=mesh_ctx)

    return (kv_donating_jit(dense, (2, 3), static_argnames=("cfg",)),
            kv_donating_jit(paged, (2, 3), static_argnames=("cfg",)))


def _pow2(n: int) -> int:
    b = 1
    while b < n:
        b *= 2
    return b


class FusedTransport:
    """One host dispatch per decode step; LUT uploads off the token path."""

    name = "fused"

    def __init__(self, server, n_adapters: Optional[int] = None,
                 mesh_ctx=None, tracer=None):
        self.server = server
        self.n_adapters = n_adapters
        self.mesh_ctx = mesh_ctx
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.stats = TransportStats(transport="fused")
        self._view: Optional[DeviceLoraView] = None
        self._fingerprint = None
        if mesh_ctx is not None:
            self._dense, self._paged = _make_fused_steps(mesh_ctx)
        else:
            self._dense, self._paged = _fused_dense, _fused_paged

    # ------------------------- residency upload ----------------------- #
    def _replicas(self):
        reps = getattr(self.server, "replicas", None)
        return list(reps) if reps is not None else [self.server]

    def _current_fingerprint(self, reps):
        return (len(reps), getattr(self.server, "version", 0),
                bool(getattr(self.server, "rank_aware", True)),
                tuple(getattr(r, "mutations", 0) for r in reps))

    def refresh(self) -> bool:
        """Re-upload the device view iff residency/replica state changed
        since the last upload. Returns True on upload."""
        reps = self._replicas()
        fp = self._current_fingerprint(reps)
        if fp == self._fingerprint and self._view is not None:
            return False
        for rep in reps:
            if getattr(rep, "mesh", None) is not None or \
                    getattr(rep, "y", 1) != 1:
                raise ValueError(
                    "FusedTransport requires single-device replicas "
                    "(y == 1, no server mesh): the stacked device pool "
                    "indexes layers directly")
            if not hasattr(rep, "pool"):
                raise ValueError(
                    "FusedTransport needs real LoRAServer replicas with "
                    "slot pools (the analytic plane has none)")
        R = len(reps)
        max_aid = max((a for rep in reps for a in rep.slot_of), default=-1)
        need = max(self.n_adapters or 0, max_aid + 1, 1) + 1
        lut = np.full(_pow2(need), -1, np.int32)
        for i, rep in enumerate(reps):
            for aid, slot in rep.slot_of.items():
                if aid % R == i and aid < len(lut):
                    lut[aid] = slot
        stacked = {name: jnp.stack([rep.pool[name][0] for rep in reps])
                   for name in ("up_A", "up_B", "down_A", "down_B")}
        lut_arr = jnp.asarray(lut)
        # per-slot true ranks ride along with the residency upload; with
        # rank awareness off every slot pays the padded pool rank, which
        # makes the device-side mask all-true (the padded baseline)
        if getattr(self.server, "rank_aware", True):
            ranks_np = np.stack([np.where(
                np.asarray(rep.slot_ranks) > 0,
                np.asarray(rep.slot_ranks), rep.r).astype(np.int32)
                for rep in reps])
        else:
            ranks_np = np.stack([np.full(len(rep.slot_ranks), rep.r,
                                         np.int32) for rep in reps])
        ranks_arr = jnp.asarray(ranks_np)
        if self.mesh_ctx is not None:
            # control-plane DMA onto the mesh (replicated): the fused step
            # mixes the view with mesh-committed params/KV, so the view
            # must share their device assignment
            repl = jax.sharding.NamedSharding(
                self.mesh_ctx.mesh, jax.sharding.PartitionSpec())
            stacked = {n: jax.device_put(a, repl)
                       for n, a in stacked.items()}
            lut_arr = jax.device_put(lut_arr, repl)
            ranks_arr = jax.device_put(ranks_arr, repl)
        self._view = DeviceLoraView(stacked["up_A"], stacked["up_B"],
                                    stacked["down_A"], stacked["down_B"],
                                    lut_arr, ranks_arr)
        self._fingerprint = fp
        self.stats.lut_uploads += 1
        return True

    # ---------------------------- decode step ------------------------- #
    def decode_step(self, params, cfg, k, v, toks, pos_vec, adapter_ids,
                    lora_scale, *, sel=None, scatter_idx=None,
                    block_table=None):
        tr = self.tracer
        with tr.scope("serve.transport.refresh"):
            self.refresh()
        st = self.stats
        st.steps += 1
        st.host_dispatches += 1          # the ONE fused program launch
        st.observe_ranks(self.server, adapter_ids)
        scale = jnp.asarray(lora_scale, F32)
        with tr.scope("serve.engine.dispatch"):
            if block_table is not None:
                return self._paged(params, cfg, k, v, block_table, toks,
                                   pos_vec, self._view, adapter_ids, scale)
            return self._dense(params, cfg, k, v, sel, scatter_idx, toks,
                               pos_vec, self._view, adapter_ids, scale)


@functools.partial(jax.jit, static_argnames=("hook", "layer"))
def fused_hook_delta(view: DeviceLoraView, hook: str, layer: int, rows,
                     adapter_ids, expert_ids):
    """Standalone jitted hook delta through the device view (bench/test
    entry point — the serving path embeds ``view.compute`` inside the full
    fused step instead)."""
    return view.compute(hook, layer, rows, adapter_ids, expert_ids)
