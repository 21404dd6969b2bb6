"""The disaggregated LoRA Server (paper §3-§5).

A LoRA Server owns a slot pool of resident adapters (cache capacity M) and
executes LoRA deltas for remote LLM instances. Execution is SPMD over a
dedicated server mesh with axes ("ep", "pp") implementing the paper's hybrid
EP_x-PP_y layout: experts block-sharded over "ep", layers interleaved over
"pp" stages (layer l -> stage l % y), adapters replicated within a stage.

Per MoE layer the server is invoked twice (paper Fig. 7b):
  hook "up"   : rows x  (R, d)  -> concat gate/up deltas (R, n_up*ff)
  hook "down" : rows h  (R, ff) -> down delta (R, d)

Rows arrive *aligned by expert partition* (paper §4.1 aligned expert
partitioning: each server device receives only rows for its experts), i.e.
sharded P("ep") on the row dim. One compiled step per hook serves every
layer via a traced layer index into the stage's interleaved stack.

On real hardware the client->server transfer is the resharding DMA between
the instance mesh and this server mesh (push semantics; see DESIGN.md §3);
in this container both meshes are host devices and the demo runs the same
code path end to end.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from repro.configs.base import ModelConfig
from repro.core.adapter import AdapterPool
from repro.core.placement import Placement

F32 = jnp.float32


def make_server_mesh(x: int, y: int, devices=None) -> Mesh:
    devices = np.asarray(devices if devices is not None
                         else jax.devices()[: x * y]).reshape(x, y)
    return Mesh(devices, ("ep", "pp"))


@dataclasses.dataclass
class ServerConfig:
    m: int                       # server device count
    x: int                       # EP degree
    y: int                       # PP stages (x*y == m)
    cache_slots: int             # M — resident adapter capacity
    rank: int
    targets_up: Tuple[str, ...] = ("gate", "up")
    target_down: str = "down"


class LoRAServer:
    """Host-side server object: slot table + compiled SPMD steps."""

    def __init__(self, model_cfg: ModelConfig, server_cfg: ServerConfig,
                 pool_init_key=None, mesh: Optional[Mesh] = None,
                 dtype=jnp.bfloat16, abstract: bool = False):
        """``abstract``: hold ShapeDtypeStructs instead of buffers — used by
        the dry-run to lower/compile the server steps without allocating a
        multi-GB slot pool on the host."""
        self.cfg = model_cfg
        self.scfg = server_cfg
        self.mesh = mesh
        E = max(model_cfg.n_experts, 1)
        L, M, r = model_cfg.n_layers, server_cfg.cache_slots, server_cfg.rank
        d, ff = model_cfg.d_model, model_cfg.d_ff
        self.E, self.L, self.M, self.r = E, L, M, r
        # stage-interleaved layer stacks: stage s holds layers {l : l%y == s}
        self.y = server_cfg.y
        self.x = server_cfg.x
        self.L_stage = -(-L // self.y)
        gated = model_cfg.gated_mlp
        self.n_up = 2 if gated else 1

        def zeros(shape):
            if abstract:
                return jax.ShapeDtypeStruct(shape, dtype)
            if pool_init_key is None:
                return jnp.zeros(shape, dtype)
            return (jax.random.normal(pool_init_key, shape, F32) * 0.01
                    ).astype(dtype)

        # slot pools, layer-major within stage: (y, L_stage, M, E, ...).
        # gate and up have independent A factors, so the fused "up" hook
        # operator has rank n_up*r (block-diagonal B).
        ru = self.n_up * r
        self.pool = {
            "up_A": zeros((self.y, self.L_stage, M, E, d, ru)),
            "up_B": zeros((self.y, self.L_stage, M, E, ru, self.n_up * ff)),
            "down_A": zeros((self.y, self.L_stage, M, E, ff, r)),
            "down_B": zeros((self.y, self.L_stage, M, E, r, d)),
        }
        # adapter id -> slot (host table); -1 = not resident
        self.slot_of: Dict[int, int] = {}
        self.free_slots = list(range(M))
        # per-slot TRUE rank (0 = empty slot): a mixed-rank pool stores a
        # rank-4 adapter in rank-r lanes whose tail is exactly +0.0, so the
        # compute step can bound each row's contraction at its true rank
        # bit-identically. Kept in sync through insert/evict (and re-homes,
        # which are evict+insert).
        self.slot_ranks = np.zeros(M, np.int32)
        # rank_aware=False pins the padded-pool-rank compute path (the
        # bit-identity baseline; also what pre-rank-aware callers got)
        self.rank_aware = True
        self._steps = {}
        self._lut = None  # cached id->slot array, invalidated on insert/evict
        # monotone residency/weight mutation counter: the fused transport
        # fingerprints it to re-upload its device-resident LUT + stacked
        # pools ONLY when something actually changed (never per token)
        self.mutations = 0

    # ------------------------------------------------------------------ #
    # residency management (driven by serving.cache's policy)             #
    # ------------------------------------------------------------------ #
    def is_resident(self, adapter_id: int) -> bool:
        return adapter_id in self.slot_of

    def insert(self, adapter_id: int, tensors=None,
               layers: Optional[range] = None,
               rank: Optional[int] = None) -> int:
        """Claim a slot (loading itself is timed by the serving simulator;
        tensors, when given, are written layer-wise — §5.3). ``rank`` is
        the adapter's TRUE rank (defaults to the pool rank — i.e. no
        trimming for this slot)."""
        if adapter_id in self.slot_of:
            return self.slot_of[adapter_id]
        if not self.free_slots:
            raise RuntimeError("LoRA server cache full")
        slot = self.free_slots.pop(0)
        self.slot_of[adapter_id] = slot
        self.slot_ranks[slot] = int(rank) if rank else self.r
        self._lut = None
        self.mutations += 1
        if tensors is not None:
            self._write_slot(slot, tensors, layers)
        return slot

    def evict(self, adapter_id: int):
        slot = self.slot_of.pop(adapter_id)
        self.free_slots.append(slot)
        self.slot_ranks[slot] = 0
        self._lut = None
        self.mutations += 1

    def _write_slot(self, slot: int, tensors, layers=None):
        """tensors: {'up_A': (L, E, d, r), ...} full-layer stacks."""
        L = self.L
        layers = layers if layers is not None else range(L)
        for name in self.pool:
            src = tensors[name]
            buf = self.pool[name]
            for l in layers:
                s, li = l % self.y, l // self.y
                buf = buf.at[s, li, slot].set(src[l].astype(buf.dtype))
            self.pool[name] = buf
        self.mutations += 1

    # ------------------------------------------------------------------ #
    # compiled steps                                                      #
    # ------------------------------------------------------------------ #
    def _specs(self, row_dim_sharded: bool):
        if self.mesh is None:
            return None
        return P("ep") if row_dim_sharded else P()

    def _step(self, hook: str):
        """Compiled (layer, rows, slot_ids, expert_ids) -> deltas."""
        if hook in self._steps:
            return self._steps[hook]
        cfg, E, r = self.cfg, self.E, self.r
        d, ff = cfg.d_model, cfg.d_ff
        n_up, y = self.n_up, self.y

        def body(stage_idx, layer_idx, rows, slots, eids, ranks, A, B):
            # A: (L_stage, M, E_loc, d_in, r) local shard on ep
            A_l = jax.lax.dynamic_index_in_dim(A, layer_idx, 0, False)
            B_l = jax.lax.dynamic_index_in_dim(B, layer_idx, 0, False)
            slots_safe = jnp.maximum(slots, 0)
            a = A_l[slots_safe, eids]          # (R_loc, d_in, r)
            b = B_l[slots_safe, eids]          # (R_loc, r, d_out)
            h = jnp.einsum("td,tdr->tr", rows.astype(F32), a.astype(F32))
            # true-rank bound per row: the fused "up" hook is block-diagonal
            # (gate cols 0..r-1, up cols r..2r-1), so an adapter of true
            # rank k occupies column k of EACH r-wide block — mask on
            # col % r. Masked lanes already hold the pool's exact +/-0
            # padding, so forcing +0.0 never changes a token.
            col = jax.lax.broadcasted_iota(jnp.int32, h.shape, 1)
            h = jnp.where((col % r) < ranks[:, None], h, 0.0)
            out = jnp.einsum("tr,tro->to", h, b.astype(F32))
            return jnp.where((slots >= 0)[:, None], out, 0.0)

        if self.mesh is not None:
            E_loc = max(E // self.x, 1)

            def sharded(stage_idx, layer_idx, rows, slots, eids, ranks,
                        A, B):
                def local(rows_l, slots_l, eids_l, ranks_l, A_l, B_l):
                    # rows arrive expert-block-aligned per ep rank (§4.1
                    # aligned partitioning): local expert id within the block
                    e_local = eids_l % E_loc
                    out = body(stage_idx, layer_idx, rows_l, slots_l,
                               e_local, ranks_l, A_l[0], B_l[0])
                    # only the owning pipeline stage computes this layer; the
                    # others (serving other instances' layers in steady
                    # state) contribute zeros.
                    mine = jax.lax.axis_index("pp") == (stage_idx % y)
                    return jax.lax.psum(jnp.where(mine, out, 0.0), "pp")

                return shard_map(
                    local, mesh=self.mesh,
                    in_specs=(P("ep"), P("ep"), P("ep"), P("ep"),
                              P("pp", None, None, "ep", None, None),
                              P("pp", None, None, "ep", None, None)),
                    out_specs=P("ep"), check_vma=False,
                )(rows, slots, eids, ranks, A, B)

            fn = jax.jit(sharded, static_argnums=(0,))
        else:
            def flat(stage_idx, layer_idx, rows, slots, eids, ranks, A, B):
                return body(stage_idx, layer_idx, rows, slots, eids, ranks,
                            A[stage_idx], B[stage_idx])
            fn = jax.jit(flat, static_argnums=(0,))
        self._steps[hook] = fn
        return fn

    def resolve_slots(self, adapter_ids) -> np.ndarray:
        """Map (R,) global adapter ids -> resident slot ids (-1 = absent /
        inactive row). The LUT is cached across calls — one decode step hits
        this 2 x n_layers times — and rebuilt only after insert/evict."""
        if self._lut is None:
            lut = np.full(max(self.slot_of, default=0) + 2, -1, np.int32)
            for aid, slot in self.slot_of.items():
                lut[aid] = slot
            self._lut = lut
        lut = self._lut
        ids = np.asarray(adapter_ids)
        return np.where((ids >= 0) & (ids < len(lut)),
                        lut[np.clip(ids, 0, len(lut) - 1)], -1)

    def row_ranks(self, slots: np.ndarray) -> np.ndarray:
        """Per-row true-rank bound for resolved slots: the slot's true rank
        when rank_aware, else the pool rank (the padded baseline).
        Inactive rows get the pool rank — they are masked to zero anyway."""
        if not self.rank_aware:
            return np.full(len(slots), self.r, np.int32)
        ranks = self.slot_ranks[np.maximum(slots, 0)]
        return np.where((slots >= 0) & (ranks > 0), ranks,
                        self.r).astype(np.int32)

    def true_rank(self, adapter_id: int) -> int:
        """TRUE rank of a resident adapter (0 = not resident)."""
        slot = self.slot_of.get(adapter_id)
        return int(self.slot_ranks[slot]) if slot is not None else 0

    def compute(self, hook: str, layer: int, rows, adapter_ids, expert_ids):
        """rows: (R, d_in); adapter_ids: (R,) global ids (resolved to slots
        here); expert_ids: (R,). Returns deltas (R, d_out) f32."""
        stage, li = layer % self.y, layer // self.y
        slots_np = self.resolve_slots(adapter_ids)
        slots = jnp.asarray(slots_np)
        if hook == "up":
            A, B = self.pool["up_A"], self.pool["up_B"]
        else:
            A, B = self.pool["down_A"], self.pool["down_B"]
        fn = self._step(hook)
        return fn(stage, jnp.int32(li), rows, slots,
                  jnp.asarray(expert_ids, jnp.int32),
                  jnp.asarray(self.row_ranks(slots_np)), A, B)

    # ------------------------------------------------------------------ #
    def cache_bytes(self) -> int:
        return sum(int(a.size) * a.dtype.itemsize for a in self.pool.values())

    def placement(self) -> Placement:
        return Placement.make("hybrid", self.scfg.m, self.M, self.L, self.E,
                              x=self.x)


def pool_tensors_from_adapter(pool: AdapterPool, adapter_id: int):
    """Extract one adapter's server-side tensors from an AdapterPool."""
    cfg = pool.cfg
    E = max(cfg.n_experts, 1)
    L = cfg.n_layers
    gated = cfg.gated_mlp

    def tgt(name):
        t = pool.tensors[name]
        A, B = t["A"][:, adapter_id], t["B"][:, adapter_id]
        if not cfg.is_moe:  # add a singleton expert dim
            A, B = A[:, None], B[:, None]
        return A, B

    up_A, up_B = tgt("up")
    if gated and "gate" in pool.tensors:
        g_A, g_B = tgt("gate")
        # gate and up have independent A's: fuse as rank-2r with a
        # block-diagonal B so one server GEMM yields [dgate, dup].
        up_A = jnp.concatenate([g_A, up_A], axis=-1)          # (L,E,d,2r)
        up_B = jnp.concatenate(
            [jnp.concatenate([g_B, jnp.zeros_like(g_B)], axis=-1),
             jnp.concatenate([jnp.zeros_like(up_B), up_B], axis=-1)],
            axis=-2)                                          # (L,E,2r,2ff)
    dn_A, dn_B = tgt("down")
    return {"up_A": up_A, "up_B": up_B, "down_A": dn_A, "down_B": dn_B}
