#!/usr/bin/env python3
"""Bring-up smoke run of the served path on a TPU.

Serves Mixtral-8x7B at its published widths (d_model 4096, 32 query and
8 KV heads of 128, d_ff 14336, 8 experts top-2, vocab 32000) with depth cut
from 32 layers to 2, bf16 weights drawn from a seed, 8 adapters at the
config's rank 64 on every LoRA target, and a paged KV cache. Requests go
through the front door (``build_system`` -> ``submit`` -> ``drain``) by way
of ``repro.launch.serve``, as the serving driver does.

  python chip_smoke.py             # one chip
  python chip_smoke.py --chips 4   # one host of four chips (2x2)

One chip: each served-path Pallas kernel against its ``kernels/ref.py``
oracle at the served shapes, then three serving phases: coupled,
disaggregated over the host transport, disaggregated over the fused
transport. Four chips: the fused disaggregated phase on a (4, 1) mesh (two
experts per chip) and the same requests on one device, and nothing else.

Each phase serves its requests twice on one system. The first pass
compiles; the second is the steady pass. Compile seconds are the sum of
JAX's own compile events. Token ids reach the host only after the step
that made them has finished on the device, so the wall times end at the
device's end. This is a bring-up check, not a benchmark.

Exits nonzero, with no result line, when the first device is not a TPU,
when a request ends without its token count or with a token outside the
vocabulary, or when a kernel disagrees with its oracle beyond
``KERNEL_RTOL``. The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.kernels import ops, ref  # noqa: E402
from repro.launch import serve as serve_mod  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import moe  # noqa: E402
from repro.obs.clock import wall_time  # noqa: E402
from repro.serving.api import build_system  # noqa: E402

ARCH, LAYERS = "mixtral-8x7b", 2
N_ADAPTERS = 8
N_REQUESTS = 8
PROMPT_LENS = (32, 128)
NEW_TOKENS = 16
SEED = 0

# Kernel against oracle, as max|got - want| / max|want|. Both take bf16
# operands and accumulate in float32, but XLA's default-precision TPU dot
# may round the oracle's float32 intermediate (LoRA: h = x @ A; attention:
# the softmax weights) to bf16 before the second product, where the Mosaic
# kernel need not: they agree to bf16 rounding of that intermediate, 2^-8
# relative per term. 1e-2 leaves room for the sum over terms and still
# fails on a wrong row, adapter, expert or page, which moves the output by
# its own magnitude.
KERNEL_RTOL = 1e-2


class CompileClock:
    """While entered, sums JAX's compile events (trace, lowering, backend
    compile) and counts the programs compiled."""

    def __init__(self):
        self.seconds = 0.0
        self.events = 0

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on_event)

    def _on_event(self, name, secs, **_kw):
        if name.startswith("/jax/core/compile/"):
            self.seconds += secs
            self.events += name.endswith("backend_compile_duration")


def kernel_cases(cfg, pool, seed: int = SEED):
    """{name: (kernel, oracle, args)} for each served-path kernel at the
    served shapes, with the served adapters' layer-0 factors."""
    rng = np.random.default_rng(seed)
    bf = jnp.bfloat16
    T, N, E = N_REQUESTS, N_ADAPTERS, cfg.n_experts
    rows = moe.capacity(T, cfg.top_k, E, cfg.capacity_factor,
                        dropless=True) * E

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape, np.float32), bf)

    def ids(n, hi):
        return jnp.asarray(rng.integers(-1, hi, n), jnp.int32)

    def lora(target, n_rows, per_expert):
        t = pool.tensors[target]
        a, b = t["A"][0], t["B"][0]
        args = (normal(n_rows, a.shape[-2]), a, b, ids(n_rows, N))
        if per_expert:
            return (ops.bgmv_expert, ref.bgmv_expert_ref,
                    args + (jnp.asarray(rng.integers(0, E, n_rows),
                                        jnp.int32),))
        return ops.bgmv, ref.bgmv_ref, args

    KV, hd = cfg.n_kv_heads, cfg.head_dim
    ps = serve_mod.PAGE_SIZE
    nb = serve_mod.serve_config(
        disaggregated=False, transport="host", max_batch=T,
        n_adapters=N, max_prompt=PROMPT_LENS[1],
        new_tokens=NEW_TOKENS).max_len // ps
    n_pages = T * nb
    pos = rng.integers(0, nb * ps, T).astype(np.int32)
    pos[0] = -1                                     # an inactive row
    perm = rng.permutation(n_pages)
    bt = np.full((T, nb), -1, np.int32)
    for b in range(T):
        n = (pos[b] + ps) // ps if pos[b] >= 0 else 0
        bt[b, :n] = perm[b * nb: b * nb + n]
    paged = (ops.paged_attention, ref.paged_attention_ref,
             (normal(T, KV, cfg.n_heads // KV, hd),
              normal(n_pages, ps, KV, hd), normal(n_pages, ps, KV, hd),
              jnp.asarray(bt), jnp.asarray(pos)))

    return {"bgmv q": lora("q", T, False), "bgmv k": lora("k", T, False),
            "bgmv o": lora("o", T, False),
            "bgmv_expert gate": lora("gate", rows, True),
            "bgmv_expert down": lora("down", rows, True),
            "paged_attention": paged}


def check_kernel(name, kernel, oracle, args) -> float:
    """Run the kernel and its oracle once; raises beyond KERNEL_RTOL."""
    got = np.asarray(kernel(*args), np.float32)
    want = np.asarray(oracle(*args), np.float32)
    err = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
    print(f"kernel {name}: shapes {[tuple(a.shape) for a in args]} "
          f"rel_err {err:.3e} (limit {KERNEL_RTOL:g})", flush=True)
    if not np.isfinite(err) or err > KERNEL_RTOL:
        raise RuntimeError(f"{name}: kernel disagrees with its oracle "
                           f"({err:.3e} > {KERNEL_RTOL:g})")
    return err


def run_phase(name, cfg, params, pool, requests, clock, *, disaggregated,
              transport, mesh_shape=None):
    """Serve ``requests`` twice on one system; returns the first pass's
    token streams."""
    scfg = serve_mod.serve_config(
        disaggregated=disaggregated, transport=transport,
        max_batch=len(requests), n_adapters=N_ADAPTERS,
        max_prompt=PROMPT_LENS[1], new_tokens=NEW_TOKENS,
        mesh_shape=mesh_shape)
    system = build_system(scfg, cfg, params=params, pool=pool)
    try:
        passes = []
        for _ in range(2):
            c0, n0, t0 = clock.seconds, clock.events, wall_time()
            handles = serve_mod.serve(system, requests, NEW_TOKENS)
            passes.append((wall_time() - t0, clock.seconds - c0,
                           clock.events - n0, [h.tokens for h in handles]))
    finally:
        system.close()
        gc.collect()
    (w1, c1, n1, toks), (w2, c2, n2, toks2) = passes
    n_tok = sum(len(t) for t in toks)
    print(f"phase {name}: first pass {w1:.3f} s of which compile {c1:.3f} s "
          f"({n1} programs); steady pass {w2:.3f} s, compile {c2:.3f} s "
          f"({n2} programs); {n_tok} tokens per pass; passes agree: "
          f"{toks == toks2}", flush=True)
    print_memory(f"phase {name}")
    return toks


def agreement(a, b) -> float:
    """Share of token positions on which two runs of the same requests
    agree."""
    pairs = [(x, y) for ta, tb in zip(a, b) for x, y in zip(ta, tb)]
    return sum(x == y for x, y in pairs) / len(pairs)


def print_memory(after: str):
    """Each device's bytes in use and its peak since the process began, so
    that the step which raised the peak shows in the sequence of lines."""
    for d in jax.devices():
        st = d.memory_stats() or {}
        print(f"memory after {after} {d}: bytes_in_use "
              f"{st.get('bytes_in_use')} peak_bytes_in_use "
              f"{st.get('peak_bytes_in_use')} bytes_limit "
              f"{st.get('bytes_limit')}", flush=True)


def smoke(chips: int, clock: CompileClock) -> None:
    """Every phase of one run; raises on the first failure."""
    cfg = serve_mod.model_config(ARCH, layers=LAYERS)
    print(f"config {cfg.name}: d_model {cfg.d_model}, heads {cfg.n_heads} "
          f"(kv {cfg.n_kv_heads}) x {cfg.head_dim}, d_ff {cfg.d_ff}, "
          f"experts {cfg.n_experts} top-{cfg.top_k}, vocab "
          f"{cfg.vocab_size}, {cfg.dtype}; cut: {cfg.n_layers} of 32 "
          f"layers, widths as published; {N_ADAPTERS} adapters rank "
          f"{cfg.lora_rank} on {','.join(cfg.lora_targets)}; paged KV, "
          f"page {serve_mod.PAGE_SIZE}", flush=True)
    t0, c0 = wall_time(), clock.seconds
    params, pool = serve_mod.init_model(cfg, N_ADAPTERS, SEED)
    jax.block_until_ready((params, pool.tensors))
    print(f"init {wall_time() - t0:.3f} s of which compile "
          f"{clock.seconds - c0:.3f} s", flush=True)
    print_memory("init")
    requests = serve_mod.make_requests(cfg, N_REQUESTS, N_ADAPTERS,
                                       prompt_lens=PROMPT_LENS, seed=SEED)

    if chips == 4:
        single = run_phase("disagg_fused 1 device", cfg, params, pool,
                           requests, clock, disaggregated=True,
                           transport="fused")
        mesh = run_phase("disagg_fused mesh (4, 1)", cfg, params, pool,
                         requests, clock, disaggregated=True,
                         transport="fused", mesh_shape=(4, 1))
        print(f"token agreement mesh (4, 1) vs 1 device: "
              f"{agreement(single, mesh):.4f}", flush=True)
    else:
        t0, c0 = wall_time(), clock.seconds
        for name, (kernel, oracle, kargs) in kernel_cases(cfg, pool).items():
            if "tpu_custom_call" not in \
                    jax.jit(kernel).lower(*kargs).as_text():
                raise RuntimeError(f"{name}: the served path did not lower "
                                   f"to a Mosaic kernel")
            check_kernel(name, kernel, oracle, kargs)
        print(f"kernels {wall_time() - t0:.3f} s of which compile "
              f"{clock.seconds - c0:.3f} s", flush=True)
        print_memory("kernels")
        toks = {}
        for name, disagg, transport in (("coupled", False, "host"),
                                        ("disagg_host", True, "host"),
                                        ("disagg_fused", True, "fused")):
            toks[name] = run_phase(name, cfg, params, pool, requests, clock,
                                   disaggregated=disagg, transport=transport)
        # the disaggregated planes apply the expert adapters only, so they
        # are compared with each other and, separately, with coupled
        print(f"token agreement disagg_host vs disagg_fused: "
              f"{agreement(toks['disagg_host'], toks['disagg_fused']):.4f}; "
              f"coupled vs disagg_host: "
              f"{agreement(toks['coupled'], toks['disagg_host']):.4f}",
              flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: first device is {dev.platform!r}, not a TPU",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              f"device(s) visible", file=sys.stderr)
        return 1
    cache_dir = enable_compile_cache()
    print(f"device_kind {dev.device_kind}; {len(devices)} device(s); "
          f"compile cache {cache_dir}", flush=True)
    with CompileClock() as clock:
        smoke(args.chips, clock)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
