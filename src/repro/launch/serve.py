"""Serving driver: real multi-LoRA decode on this host + cluster simulation,
both through the one serving front door (``repro.serving.api``).

  # Mixtral-8x7B at its published widths, depth cut to 2 layers (one chip)
  PYTHONPATH=src python -m repro.launch.serve --arch mixtral-8x7b \
      --layers 2 --mode disagg --requests 8
  # the tiny same-family config, on the CPU
  PYTHONPATH=src JAX_PLATFORMS=cpu python -m repro.launch.serve \
      --arch dbrx-132b --reduced --mode disagg --requests 4
  PYTHONPATH=src python -m repro.launch.serve --cluster --arch mixtral-8x7b \
      --rate 25

``chip_smoke.py`` at the repository root serves through the same
functions (``model_config`` / ``init_model`` / ``make_requests`` /
``serve_config`` / ``serve``).
"""
from __future__ import annotations

import argparse
import dataclasses
from typing import List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.baselines import slora as presets
from repro.configs import ModelConfig, get_config
from repro.core import adapter as adapter_mod
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as model_mod
from repro.serving import workload
from repro.serving.api import RequestHandle, ServeConfig, ServeSystem, \
    build_system

PAGE_SIZE = 16          # KV page (tokens); the served KV cache is paged
_init_params = jax.jit(model_mod.init_params, static_argnums=0)


def model_config(arch: str, *, reduced: bool = False,
                 layers: Optional[int] = None) -> ModelConfig:
    """``arch``'s config: its published widths, or the tiny same-family
    ``reduced()`` one; ``layers`` cuts depth only, leaving every width."""
    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()
    if layers is not None:
        if not 1 <= layers <= cfg.n_layers:
            raise ValueError(f"--layers {layers} outside 1..{cfg.n_layers}")
        cfg = dataclasses.replace(cfg, n_layers=layers)
    return cfg


def init_model(cfg: ModelConfig, n_adapters: int, seed: int = 0):
    """Weights drawn from ``seed`` and ``n_adapters`` adapters at the
    config's ``lora_rank`` on all of its ``lora_targets``. The weights are
    drawn in one compiled program, so the device never holds a float32
    draw of an expert stack beside the finished bf16 weights."""
    key = jax.random.PRNGKey(seed)
    params = _init_params(cfg, key)
    pool = adapter_mod.init_adapter_pool(cfg, n_adapters,
                                         jax.random.fold_in(key, 1))
    return params, pool


def make_requests(cfg: ModelConfig, n: int, n_adapters: int, *,
                  prompt_lens: Tuple[int, int] = (8, 8), seed: int = 0
                  ) -> List[Tuple[List[int], int]]:
    """``n`` (prompt, adapter_id) pairs: seeded token ids with lengths
    drawn from ``prompt_lens`` (inclusive), adapters round-robin."""
    rng = np.random.default_rng(seed)
    lo, hi = prompt_lens
    return [(rng.integers(0, cfg.vocab_size,
                          int(rng.integers(lo, hi + 1))).tolist(),
             i % n_adapters) for i in range(n)]


def serve_config(*, disaggregated: bool, transport: str, max_batch: int,
                 n_adapters: int, max_prompt: int, new_tokens: int,
                 mesh_shape: Optional[Tuple[int, int]] = None
                 ) -> ServeConfig:
    """One real-plane instance with paged KV: every prompt prefills in one
    chunk, and a slot holds the longest prompt plus its new tokens."""
    pages = -(-(max_prompt + new_tokens) // PAGE_SIZE)
    return ServeConfig(
        backend="cluster", disaggregated=disaggregated, transport=transport,
        n_instances=1, max_batch=max_batch, max_len=pages * PAGE_SIZE,
        paged=True, page_size=PAGE_SIZE,
        prefill_chunk=-(-max_prompt // PAGE_SIZE) * PAGE_SIZE,
        adapter_cache_slots=n_adapters, mesh_shape=mesh_shape)


def serve(system: ServeSystem, requests: Sequence[Tuple[List[int], int]],
          new_tokens: int) -> List[RequestHandle]:
    """Submit ``requests`` through the front door and drain. Raises unless
    every request finished with ``new_tokens`` tokens inside the
    vocabulary."""
    handles = [system.submit(p, adapter_id=a, max_new_tokens=new_tokens)
               for p, a in requests]
    system.drain()
    vocab = system.model.vocab_size
    for h in handles:
        if h.state.name != "FINISHED" or len(h.tokens) != new_tokens:
            raise RuntimeError(f"request {h.rid} ended {h.state.name} with "
                               f"{len(h.tokens)}/{new_tokens} tokens "
                               f"({h.error or 'no error recorded'})")
        if not all(0 <= t < vocab for t in h.tokens):
            raise RuntimeError(f"request {h.rid}: token id outside the "
                               f"vocabulary of {vocab}: {h.tokens}")
    return handles


def run_local(args):
    cfg = model_config(args.arch, reduced=args.reduced, layers=args.layers)
    if not cfg.is_moe and args.mode == "disagg":
        raise SystemExit("disaggregated hooks target MoE archs; use coupled")
    params, pool = init_model(cfg, args.adapters)
    new_tokens = 8
    requests = make_requests(cfg, args.requests, args.adapters)
    system = build_system(
        serve_config(disaggregated=args.mode == "disagg", transport="host",
                     max_batch=args.requests, n_adapters=args.adapters,
                     max_prompt=max(len(p) for p, _ in requests),
                     new_tokens=new_tokens),
        cfg, params=params, pool=pool)
    try:
        handles = serve(system, requests, new_tokens)
    finally:
        system.close()
    ids = sorted({h.request.adapter_id for h in handles})
    print(f"served batch={len(handles)} adapters={ids}")
    print("generated:", [h.tokens for h in handles])
    return 0


def run_cluster(args):
    cfg = get_config(args.arch)
    reqs = workload.generate(args.adapters, rate=args.rate,
                             duration=args.duration, seed=0)
    s_cfg = ServeConfig.from_sim(presets.slora_config(
        cfg, 4, args.gpus_per_instance, args.adapters, args.duration))
    i_cfg = ServeConfig.from_sim(presets.infinilora_config(
        cfg, 3, args.gpus_per_instance, args.gpus_per_instance,
        args.adapters, args.duration))
    for name, scfg in (("s-lora", s_cfg), ("infinilora", i_cfg)):
        system = build_system(scfg, cfg)
        system.submit_workload(reqs)
        system.drain()
        s = system.summary(duration=args.duration)
        print(f"{name:12s} p95_ttft={s.p95_ttft:8.3f}s tpot={s.mean_tpot:.4f}s "
              f"thr={s.throughput_rps:7.2f}r/s attain={s.slo_attainment:.2%}")
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mixtral-8x7b")
    ap.add_argument("--reduced", action="store_true",
                    help="tiny same-family config (CPU smoke size)")
    ap.add_argument("--layers", type=int, default=None,
                    help="cut depth to N layers; every width stays")
    ap.add_argument("--mode", default="disagg", choices=["disagg", "coupled"])
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--adapters", type=int, default=8)
    ap.add_argument("--cluster", action="store_true")
    ap.add_argument("--rate", type=float, default=25.0)
    ap.add_argument("--duration", type=float, default=120.0)
    ap.add_argument("--gpus-per-instance", type=int, default=8)
    args = ap.parse_args(argv)
    enable_compile_cache()
    return run_cluster(args) if args.cluster else run_local(args)


if __name__ == "__main__":
    raise SystemExit(main())
