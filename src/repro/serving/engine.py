"""Decode engine: the REAL JAX execution path for serving (examples/tests).

The primary structure is a SLOT-BASED CONTINUOUS-BATCHING engine: the engine
owns ``n_slots`` persistent decode slots; requests are admitted into free
slots and evicted at any decode-step boundary, so a new request joins the
RUNNING batch without restarting anyone else. Each slot carries its own
position and adapter id; one ``step()`` decodes one token for every
occupied slot.

KV lives in one of two layouts:

  dense slab  : (L, n_slots, max_len, KV, hd) — every slot pays for
                ``max_len`` rows whether its request needs 8 tokens or 256
  paged pool  : (L, n_pages, page_size, KV, hd) + per-slot block tables
                (``EngineConfig.paged``) — S-LoRA-style unified paging;
                pages are allocated as positions are written and freed at
                eviction, so KV memory is bounded by actual token residency
                and admission is gated on FREE PAGES (the paper's real
                KV-capacity bound) instead of "free slot".

Prompt admission uses CHUNKED PREFILL: the prompt's first ``len-1`` tokens
run through fixed-size parallel chunks (``transformer.prefill_chunk``),
each attending over the previously cached chunks, instead of one
power-of-two-padded shot — peak activation is O(chunk) and the per-chunk
KV streams straight into slot rows or pages. Prefill is LoRA-free (under
PD disaggregation prefill runs on separate instances, paper footnote 1).

Execution is shape-bucketed: occupied slots are gathered into a contiguous
batch padded to the next power-of-two bucket, so jit compiles once per
bucket size (and once per chunk geometry for prefill) regardless of the
admission pattern. The jitted steps are MODULE-LEVEL functions taking the
(hashable, frozen) ModelConfig statically, so N engine instances of one
cluster share a single compile cache instead of recompiling per instance.
Padding rows run with position -1 (no cache write, output discarded) and
are scattered back with out-of-bounds indices in ``mode="drop"`` so a
padding duplicate can never clobber an active slot.

Both adapter modes share the slot machinery:

  coupled        : adapters applied in-model (S-LoRA batched path) — the
                   whole step is one jit per bucket
  disaggregated  : base-only client + remote LoRAServer round trips per
                   layer (host dispatch, so gather/step/scatter run eagerly)

Cluster-scale wall-clock behavior stays the simulator's job; this engine is
the functional data plane you would deploy per instance. The pre-refactor
static-batch ``prefill`` / ``decode`` API is kept as thin legacy wrappers.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import disagg as disagg_mod
from repro.core.adapter import AdapterPool
from repro.models import cache as cache_mod
from repro.models import transformer
from repro.obs.clock import wall_time
from repro.obs.trace import NO_SCOPE, NULL_TRACER
from repro.transport.base import kv_donating_jit as _kv_jit, make_transport

SLOT_FAMILIES = ("dense", "moe", "vlm")


def _bucket(n: int, cap: int) -> int:
    """Next power-of-two >= n, capped at cap (>= 1)."""
    b = 1
    while b < n:
        b *= 2
    return min(b, cap)


# ------------------------------------------------------------------ #
# module-level jitted steps (compile cache shared across instances)   #
# ------------------------------------------------------------------ #
# The caller always overwrites self._k/_v with the returned caches, so the
# old buffers are donated for in-place XLA updates — avoiding a 2x KV peak
# and a full-cache copy per decoded token (``transport.base.kv_donating_jit``
# gates donation on the backend, lazily).
@functools.partial(jax.jit, static_argnames=("cfg",))
def _decode_static(params, cfg, cache, tokens, lora_ctx):
    return transformer.decode_step(params, cfg, cache, tokens, lora_ctx)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _prefill_collect(params, cfg, tokens):
    # unembed=False: priming a cache only needs the KV stacks; the lm-head
    # GEMM over the padded prompt would be discarded work
    return transformer.forward(params, cfg, tokens, kind="decode",
                               collect_kv=True, unembed=False)


_prefill_chunk = functools.partial(jax.jit, static_argnames=("cfg",))(
    transformer.prefill_chunk)


def _coupled_slot_step_fn(params, cfg, k, v, sel, scatter_idx, toks,
                          pos_vec, lora_ctx):
    k_rows, v_rows = jnp.take(k, sel, axis=1), jnp.take(v, sel, axis=1)
    logits, k_rows, v_rows = transformer.decode_step_slots(
        params, cfg, k_rows, v_rows, toks, pos_vec, lora_ctx)
    logits = logits[:, : cfg.vocab_size]  # drop padded vocab
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    k = k.at[:, scatter_idx].set(k_rows, mode="drop")
    v = v.at[:, scatter_idx].set(v_rows, mode="drop")
    return tok, k, v


_coupled_slot_step = _kv_jit(_coupled_slot_step_fn, (2, 3),
                             static_argnames=("cfg",))


def _coupled_paged_step_fn(params, cfg, k_pool, v_pool, bt, toks, pos_vec,
                           lora_ctx):
    # the paged step needs no gather/scatter: every row reads and writes the
    # SHARED pool through its block table, so the per-token KV copies of the
    # dense path disappear entirely
    logits, k_pool, v_pool = transformer.decode_step_slots(
        params, cfg, k_pool, v_pool, toks, pos_vec, lora_ctx,
        block_table=bt)
    logits = logits[:, : cfg.vocab_size]
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return tok, k_pool, v_pool


_coupled_paged_step = _kv_jit(_coupled_paged_step_fn, (2, 3),
                              static_argnames=("cfg",))


@functools.partial(jax.jit, static_argnames=("n",))
def _gather_ctx_rows(k, v, slot, n):
    """Rows [0:n] of ``slot`` from a dense slab -> (L, 1, n, KV, hd)."""
    L, _, _, KV, hd = k.shape
    kc = jax.lax.dynamic_slice(k, (0, slot, 0, 0, 0), (L, 1, n, KV, hd))
    vc = jax.lax.dynamic_slice(v, (0, slot, 0, 0, 0), (L, 1, n, KV, hd))
    return kc, vc


@jax.jit  # pool must survive: NOT donated (recompiles per page count)
def _gather_ctx_pages(k_pool, v_pool, pages):
    """Pages of one slot's context -> (L, 1, n_pages*page_size, KV, hd)."""
    L, _, ps, KV, hd = k_pool.shape
    n = pages.shape[0]
    kc = jnp.take(k_pool, pages, axis=1).reshape(L, 1, n * ps, KV, hd)
    vc = jnp.take(v_pool, pages, axis=1).reshape(L, 1, n * ps, KV, hd)
    return kc, vc


def _write_chunk_rows_fn(k, v, k_rows, v_rows, slot, start):
    st = (0, slot, start, 0, 0)
    k = jax.lax.dynamic_update_slice(k, k_rows.astype(k.dtype), st)
    v = jax.lax.dynamic_update_slice(v, v_rows.astype(v.dtype), st)
    return k, v


_write_chunk_rows = _kv_jit(_write_chunk_rows_fn, (0, 1))


def _write_chunk_pages_fn(k_pool, v_pool, k_rows, v_rows, pages):
    """Scatter a chunk's (L, 1, w, KV, hd) KV into ``pages`` (w/ps ids;
    ids >= n_pages are dropped — unallocated tail of a padded chunk)."""
    L, _, ps, KV, hd = k_pool.shape
    n = pages.shape[0]
    kr = k_rows.reshape(L, n, ps, KV, hd).astype(k_pool.dtype)
    vr = v_rows.reshape(L, n, ps, KV, hd).astype(v_pool.dtype)
    k_pool = k_pool.at[:, pages].set(kr, mode="drop")
    v_pool = v_pool.at[:, pages].set(vr, mode="drop")
    return k_pool, v_pool


_write_chunk_pages = _kv_jit(_write_chunk_pages_fn, (0, 1))


@dataclasses.dataclass
class EngineConfig:
    max_len: int = 256
    kv_quant: bool = False
    greedy: bool = True
    n_slots: int = 8               # continuous-batching decode slots
    cache_dtype: Optional[object] = None  # None -> kv_dtype(kv_quant)
    # paged KV pool (tentpole): block-granular allocation instead of the
    # dense n_slots x max_len slab
    paged: bool = False
    page_size: int = 8
    n_pages: Optional[int] = None  # None -> n_slots * ceil(max_len/page)
    # admission prefill chunk width (tokens); rounded up to a page multiple
    # in paged mode
    prefill_chunk: int = 16


@dataclasses.dataclass
class SlotState:
    rid: int
    adapter_id: int
    pos: int            # position of the NEXT token fed to the model
    last_token: int     # next decode input


class Engine:
    def __init__(self, cfg: ModelConfig, params, ecfg: EngineConfig,
                 pool: Optional[AdapterPool] = None,
                 server=None, transport="host", mesh_ctx=None,
                 tracer=None):
        # ``server`` is anything satisfying LoRAServer's ``compute``
        # contract: a single LoRAServer or an elastic ``ServerPool`` of
        # replicas (serving/server_pool.py). The engine never dispatches
        # hooks itself — the ``transport`` plane does: "host" (per-hook
        # host round trips, the measurable baseline) or "fused" (the whole
        # disagg step as one jitted program). A prebuilt Transport instance
        # may be passed instead of a name so a cluster's engines share one
        # stats ledger and device view. ``mesh_ctx`` (an
        # ``ExpertParallelCtx``) runs the disaggregated step's base expert
        # GEMMs expert-parallel over its mesh; the KV slab/pool is then
        # committed to the mesh so the step never mixes device assignments.
        # ``tracer`` (repro.obs) takes the step's and the prefill's
        # serve.* scopes, the decode bucket counter and, on the
        # disaggregated plane, the rows each LoRA hook call computes.
        self.cfg = cfg
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.params = params
        self.ecfg = ecfg
        self.pool = pool
        self.server = server
        self.mesh_ctx = mesh_ctx
        if mesh_ctx is not None and server is None:
            raise ValueError(
                "mesh_ctx requires the disaggregated plane (server=): the "
                "coupled step's allgather MoE reassociates floats under a "
                "mesh, breaking the token bit-identity invariant")
        self.transport = None
        if server is not None:
            self.transport = transport if not isinstance(transport, str) \
                else make_transport(transport, server,
                                    n_adapters=pool.n if pool else None,
                                    mesh_ctx=mesh_ctx)
        # slot cache is lazily allocated on the first add_request so legacy
        # static-batch users don't pay the slab/pool twice
        self._k = self._v = None
        self.slots: List[Optional[SlotState]] = [None] * ecfg.n_slots
        self._by_rid: Dict[int, int] = {}
        self._chunk = max(int(ecfg.prefill_chunk), 1)
        if ecfg.paged:
            ps = int(ecfg.page_size)
            if ps < 1:
                raise ValueError(f"page_size must be >= 1, got {ps}")
            if ecfg.max_len % ps:
                raise ValueError(
                    f"paged engine needs page_size ({ps}) to divide "
                    f"max_len ({ecfg.max_len})")
            self._chunk = -(-self._chunk // ps) * ps  # page multiple
            self.blocks_per_slot = ecfg.max_len // ps
            self.total_pages = ecfg.n_pages if ecfg.n_pages is not None \
                else ecfg.n_slots * self.blocks_per_slot
            self._bt = np.full((ecfg.n_slots, self.blocks_per_slot), -1,
                               np.int32)
            self._free: List[int] = list(range(self.total_pages - 1, -1, -1))
            self.peak_pages = 0
        self._chunk = min(self._chunk, ecfg.max_len)

    # ------------------------------------------------------------------ #
    # slot admission / eviction (continuous batching control surface)     #
    # ------------------------------------------------------------------ #
    @property
    def n_slots(self) -> int:
        return len(self.slots)

    def free_slots(self) -> int:
        return sum(1 for s in self.slots if s is None)

    def active_rids(self) -> List[int]:
        return [s.rid for s in self.slots if s is not None]

    def has_request(self, rid: int) -> bool:
        return rid in self._by_rid

    def free_pages(self) -> int:
        """Unallocated pages in the paged pool (the KV admission bound)."""
        if not self.ecfg.paged:
            raise RuntimeError("free_pages() requires EngineConfig.paged")
        return len(self._free)

    def kv_stats(self) -> Dict[str, int]:
        """KV occupancy accounting for BOTH layouts: slot occupancy always;
        page-pool occupancy vs the dense-slab equivalent when paged. This is
        the observable the cancellation contract checks — after a cancel,
        slots_in_use (and pages_in_use, paged) must return to their
        pre-admission values."""
        dtype = self.ecfg.cache_dtype or cache_mod.kv_dtype(False)
        out = {
            "n_slots": self.n_slots,
            "slots_in_use": self.n_slots - self.free_slots(),
            "dense_slab_bytes": cache_mod.dense_cache_bytes(
                self.cfg, self.n_slots, self.ecfg.max_len, dtype),
        }
        if self.ecfg.paged:
            out.update(
                page_size=self.ecfg.page_size,
                n_pages=self.total_pages,
                pages_in_use=self.total_pages - len(self._free),
                peak_pages=self.peak_pages,
                pool_bytes=cache_mod.paged_cache_bytes(
                    self.cfg, self.total_pages, self.ecfg.page_size, dtype),
            )
        return out

    def transport_stats(self) -> Dict:
        """Launch accounting of the disaggregated transport plane (empty in
        coupled mode, where the whole step is one jit by construction)."""
        return self.transport.stats.as_dict() if self.transport else {}

    def _alloc_page(self) -> int:
        p = self._free.pop()
        self.peak_pages = max(self.peak_pages,
                              self.total_pages - len(self._free))
        return p

    def _ensure_slot_cache(self) -> None:
        if self._k is not None:
            return
        fam = self.cfg.family
        if fam not in SLOT_FAMILIES:
            # init_cache for these families has no per-slot "k"/"v" rows; a
            # bare KeyError('k') here was the only symptom before
            raise ValueError(
                f"slot engine requires a per-slot attention KV cache; "
                f"family '{fam}' has none (supported: "
                f"{', '.join(SLOT_FAMILIES)}). Use the legacy "
                f"prefill/decode API for ssm/hybrid/audio models.")
        if self.ecfg.kv_quant and self.ecfg.cache_dtype is None:
            # decode_step_slots does not thread k_scale/v_scale; an int8
            # cache here would be unscaled truncation -> garbage tokens
            raise ValueError(
                "slot engine does not support int8 KV quantization; "
                "use the legacy prefill/decode API for kv_quant")
        dtype = self.ecfg.cache_dtype or \
            cache_mod.kv_dtype(self.ecfg.kv_quant)
        if self.ecfg.paged:
            pool = cache_mod.init_paged_cache(
                self.cfg, self.total_pages, self.ecfg.page_size, dtype=dtype)
            self._k, self._v = pool["k"], pool["v"]
        else:
            full = cache_mod.init_cache(self.cfg, self.n_slots,
                                        self.ecfg.max_len, dtype=dtype)
            self._k, self._v = full["k"], full["v"]
        if self.mesh_ctx is not None:
            # commit the KV onto the mesh (replicated) once: params and the
            # fused view live there, and a jit mixing mesh-committed and
            # single-device-committed operands is an error, not a transfer
            from jax.sharding import NamedSharding, PartitionSpec
            repl = NamedSharding(self.mesh_ctx.mesh, PartitionSpec())
            self._k = jax.device_put(self._k, repl)
            self._v = jax.device_put(self._v, repl)

    def add_request(self, rid: int, prompt: Sequence[int],
                    adapter_id: int) -> int:
        """Admit a request into a free slot at a decode-step boundary: prime
        the slot's KV with the prompt (all but the last token) via chunked
        prefill, leaving the running batch untouched. In paged mode the
        prompt's pages are allocated here (admission requires free pages to
        cover it; later decode pages are allocated incrementally in
        ``step``). Returns the slot index."""
        if rid in self._by_rid:
            raise ValueError(f"rid {rid} already running")
        slot = next((i for i, s in enumerate(self.slots) if s is None), None)
        if slot is None:
            raise RuntimeError("no free decode slot")
        self._ensure_slot_cache()
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        plen = int(prompt.shape[0])
        # plen == max_len still fits: only plen-1 prompt tokens are written
        # and the first decode write lands at position plen-1 <= max_len-1
        if plen < 1 or plen > self.ecfg.max_len:
            raise ValueError(f"prompt length {plen} vs max_len")
        if self.ecfg.paged:
            need = cache_mod.pages_for(plen - 1, self.ecfg.page_size)
            if need > len(self._free):
                raise RuntimeError(
                    f"rid {rid}: free KV pages ({len(self._free)}) do not "
                    f"cover the prompt ({need} pages) — the scheduler must "
                    f"gate admission on free_pages()")
            for j in range(need):
                self._bt[slot, j] = self._alloc_page()
        if plen > 1:
            plan = self._chunk_plan(plen - 1)
            tr = self.tracer
            with tr.scope("serve.prefill", rid=rid, chunks=len(plan),
                          tokens=plen - 1,
                          padded_tokens=sum(w - m for _, w, m in plan)) \
                    if tr.enabled else NO_SCOPE:
                self._prefill_slot(slot, prompt[:-1], plan)
        self.slots[slot] = SlotState(rid=rid, adapter_id=int(adapter_id),
                                     pos=plen - 1,
                                     last_token=int(prompt[-1]))
        self._by_rid[rid] = slot
        return slot

    def _chunk_plan(self, n_tok: int):
        """[(start, width, real tokens)] of the prefill chunks over
        ``n_tok`` prompt tokens: fixed-width chunks, the last one padded
        (width minus real tokens is its padding)."""
        plan = []
        for c in range(0, n_tok, self._chunk):
            w = min(self._chunk, self.ecfg.max_len - c)  # writes in the slot
            plan.append((c, w, min(w, n_tok - c)))
        return plan

    def _prefill_slot(self, slot: int, toks: np.ndarray, plan) -> None:
        """Chunked prefill: run ``toks`` through fixed-width parallel
        chunks (``plan``, from ``_chunk_plan``), each attending over the
        already-cached context, writing each chunk's KV into the slot's
        rows (dense) or pages (paged). The final chunk is zero-padded to
        its width; the padded positions' KV is garbage but sits beyond
        the slot position, so it is masked by every attention until
        decode overwrites it."""
        ps = self.ecfg.page_size
        for c, w, m in plan:
            chunk = np.zeros((1, w), np.int32)
            chunk[0, :m] = toks[c:c + m]
            if self.ecfg.paged:
                pages = jnp.asarray(self._bt[slot, : c // ps])
                k_ctx, v_ctx = _gather_ctx_pages(self._k, self._v, pages)
            else:
                k_ctx, v_ctx = _gather_ctx_rows(self._k, self._v,
                                                jnp.int32(slot), c)
            chunk_j = jnp.asarray(chunk)
            k_c, v_c = _prefill_chunk(self.params, self.cfg, chunk_j, k_ctx,
                                      v_ctx)
            if self.ecfg.paged:
                # w <= max_len - c keeps this slice fully in the block
                # table; unallocated tail pages (padded final chunk) map to
                # total_pages -> write dropped
                have = self._bt[slot, c // ps: c // ps + w // ps]
                pg = np.where(have < 0, self.total_pages,
                              have).astype(np.int32)
                self._k, self._v = _write_chunk_pages(
                    self._k, self._v, k_c, v_c, jnp.asarray(pg))
            else:
                self._k, self._v = _write_chunk_rows(
                    self._k, self._v, k_c, v_c, jnp.int32(slot),
                    jnp.int32(c))

    def evict_request(self, rid: int) -> None:
        """Free a slot at a step boundary (finish or preemption). Dense: the
        KV rows are left in place (a later occupant masks them via its own
        position vector). Paged: the slot's pages return to the free pool —
        the memory actually comes back."""
        slot = self._by_rid.pop(rid)
        self.slots[slot] = None
        if self.ecfg.paged:
            self._free.extend(int(p) for p in self._bt[slot] if p >= 0)
            self._bt[slot, :] = -1

    def release_kv(self) -> None:
        """Drop the KV slab/pool of an EMPTY engine (autoscaler scale-in:
        a drained instance's memory actually comes back). The lazy
        ``_ensure_slot_cache`` re-allocates if the instance is ever
        revived."""
        if self._by_rid:
            raise RuntimeError(
                f"release_kv with {len(self._by_rid)} requests resident")
        self._k = self._v = None
        if self.ecfg.paged:
            self._bt[:] = -1
            self._free = list(range(self.total_pages - 1, -1, -1))

    # ------------------------------------------------------------------ #
    # continuous-batching decode step                                     #
    # ------------------------------------------------------------------ #
    def step(self) -> Dict[int, int]:
        """Decode ONE token for every occupied slot; returns {rid: token}.

        Gathers occupied slots into a power-of-two bucket (one jit compile
        per bucket size), pads with inactive rows (pos -1, adapter -1), and
        scatters the updated KV rows back (padding rows dropped). Paged
        mode allocates each row's next page on demand and steps through the
        shared pool directly — no gather/scatter copies."""
        occupied = [i for i, s in enumerate(self.slots) if s is not None]
        if not occupied:
            return {}
        tr = self.tracer
        with tr.scope("serve.engine.prepare"):
            nb, args = self._prepare(occupied)
        if tr.enabled:
            now = wall_time()
            tr.counter("engine", "decode_bucket", now, nb)
            if self.server is not None:
                tr.counter("engine", "hook_rows", now,
                           disagg_mod.hook_rows(self.cfg, nb))
        sel_j, sc_j, toks_j, pos_j, ads_j, bt_j = args

        if self.server is not None:
            tok, self._k, self._v = self.transport.decode_step(
                self.params, self.cfg, self._k, self._v, toks_j, pos_j,
                ads_j, self.pool.scale if self.pool else 1.0,
                sel=sel_j, scatter_idx=sc_j, block_table=bt_j)
        else:
            lora_ctx = None
            if self.pool is not None:
                lora_ctx = self.pool.lora_ctx(ads_j)
            with tr.scope("serve.engine.dispatch"):
                if self.ecfg.paged:
                    tok, self._k, self._v = _coupled_paged_step(
                        self.params, self.cfg, self._k, self._v, bt_j,
                        toks_j, pos_j, lora_ctx)
                else:
                    tok, self._k, self._v = _coupled_slot_step(
                        self.params, self.cfg, self._k, self._v, sel_j,
                        sc_j, toks_j, pos_j, lora_ctx)

        with tr.scope("serve.engine.sync"):
            tok = np.asarray(tok)
        with tr.scope("serve.engine.emit"):
            out: Dict[int, int] = {}
            for row, i in enumerate(occupied):
                s = self.slots[i]
                t = int(tok[row])
                s.pos += 1
                s.last_token = t
                out[s.rid] = t
        return out

    def _prepare(self, occupied: List[int]):
        """The step's host side: the power-of-two bucket, each row's page
        allocated on demand (paged), and the batch's arrays uploaded.
        Returns (bucket, (sel, scatter_idx, toks, pos, adapter ids,
        block table or None) on the device)."""
        nb = _bucket(len(occupied), self.n_slots)
        sel = np.zeros(nb, np.int32)
        sel[: len(occupied)] = occupied
        # padding rows scatter to index n_slots: out of bounds -> dropped
        scatter_idx = np.full(nb, self.n_slots, np.int32)
        scatter_idx[: len(occupied)] = occupied
        toks = np.zeros((nb, 1), np.int32)
        pos_vec = np.full(nb, -1, np.int32)
        ads = np.full(nb, -1, np.int32)
        for row, i in enumerate(occupied):
            s = self.slots[i]
            if s.pos >= self.ecfg.max_len:
                # the per-row write clips to max_len-1, which would silently
                # clobber the last cache cell — fail loudly instead
                raise RuntimeError(
                    f"rid {s.rid} exhausted slot KV capacity "
                    f"(pos {s.pos} >= max_len {self.ecfg.max_len})")
            if self.ecfg.paged:
                pidx = s.pos // self.ecfg.page_size
                if self._bt[i, pidx] < 0:
                    if not self._free:
                        raise RuntimeError(
                            f"rid {s.rid}: KV page pool exhausted "
                            f"mid-decode (admission over-committed "
                            f"{self.total_pages} pages)")
                    self._bt[i, pidx] = self._alloc_page()
            toks[row, 0] = s.last_token
            pos_vec[row] = s.pos
            ads[row] = s.adapter_id
        bt_j = jnp.asarray(self._bt[sel]) if self.ecfg.paged else None
        return nb, (jnp.asarray(sel), jnp.asarray(scatter_idx),
                    jnp.asarray(toks), jnp.asarray(pos_vec),
                    jnp.asarray(ads), bt_j)

    # ------------------------------------------------------------------ #
    # legacy static-batch API (quickstart / launch.serve / test_system)    #
    # ------------------------------------------------------------------ #
    def prefill(self, tokens: jax.Array, frontend_emb=None) -> Dict:
        """tokens: (B, S_prompt) -> cache primed with the prompt.

        Attention LMs run the prompt through ONE parallel
        ``forward(collect_kv=True)`` (the same path slot admission uses);
        the old implementation replayed it one token at a time through
        ``decode_step`` — O(S) sequential dispatches for identical math.
        Recurrent/audio families keep the replay (their stateful caches
        are only advanced by decode steps)."""
        B, S = tokens.shape
        cache = cache_mod.init_cache(self.cfg, B, self.ecfg.max_len,
                                     self.ecfg.kv_quant)
        if (self.cfg.family in SLOT_FAMILIES and S > 0
                and frontend_emb is None):
            if S > self.ecfg.max_len:
                raise ValueError(f"prompt length {S} vs max_len")
            _, (k_rows, v_rows) = _prefill_collect(self.params, self.cfg,
                                                   tokens)
            zero = (0, 0, 0, 0, 0)
            if self.ecfg.kv_quant:
                kq, ks = cache_mod.quantize_kv(k_rows)
                vq, vs = cache_mod.quantize_kv(v_rows)
                cache["k"] = jax.lax.dynamic_update_slice(cache["k"], kq,
                                                          zero)
                cache["v"] = jax.lax.dynamic_update_slice(cache["v"], vq,
                                                          zero)
                cache["k_scale"] = jax.lax.dynamic_update_slice(
                    cache["k_scale"], ks, zero)
                cache["v_scale"] = jax.lax.dynamic_update_slice(
                    cache["v_scale"], vs, zero)
            else:
                cache["k"] = jax.lax.dynamic_update_slice(
                    cache["k"], k_rows.astype(cache["k"].dtype), zero)
                cache["v"] = jax.lax.dynamic_update_slice(
                    cache["v"], v_rows.astype(cache["v"].dtype), zero)
            cache["pos"] = jnp.asarray(S, jnp.int32)
            return cache
        # recurrent/audio/frontend paths: replay through decode steps
        for t in range(S):
            _, cache = _decode_static(self.params, self.cfg, cache,
                                      tokens[:, t:t + 1], None)
        return cache

    def decode(self, cache: Dict, last_token: jax.Array, steps: int,
               adapter_ids: Optional[jax.Array] = None) -> jax.Array:
        """Greedy-decode ``steps`` tokens. adapter_ids: (B,) per sequence."""
        out = []
        tok = last_token
        lora_ctx = None
        if adapter_ids is not None and self.pool is not None and \
                self.server is None:
            lora_ctx = self.pool.lora_ctx(adapter_ids)
        for _ in range(steps):
            if self.server is not None and adapter_ids is not None:
                logits, cache = disagg_mod.disagg_decode_step(
                    self.params, self.cfg, cache, tok, self.server,
                    adapter_ids, self.pool.scale if self.pool else 1.0)
            else:
                logits, cache = _decode_static(self.params, self.cfg, cache,
                                               tok, lora_ctx)
            logits = logits[:, : self.cfg.vocab_size]  # drop padded vocab
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
            out.append(tok)
        return jnp.concatenate(out, axis=1)
