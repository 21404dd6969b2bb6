"""Open-loop Poisson arrivals with lognormal prompt and output lengths and
zipf adapter popularity (the arithmetic of ``serving/workload.generate``).

Every seed gets the same work on the same schedule: the inter-arrival
gaps are the exponential distribution's quantiles at (i + 1/2) / N, the
lengths the lognormal's quantiles, each put in an order drawn once from
the mix's ``schedule_seed`` (0 where the file gives none). The seed draws
the prompt token ids and which adapter each request names (zipf's shares
of N, rounded by largest remainder, over popularity ranks that the seed
maps to adapter ids). So two seeds differ in content, never in the
amount or the timing of the work: in a window of some tens of requests
the order alone moves the tails and the tokens that land inside the
window by more than a run's own noise.
"""
from __future__ import annotations

import math
from statistics import NormalDist
from typing import Dict, List

import numpy as np

from workload import Request


def _lognormal_quantiles(spec: Dict, n: int) -> np.ndarray:
    inv = NormalDist().inv_cdf
    q = [math.exp(math.log(spec["median"]) + spec["sigma"] * inv((i + .5) / n))
         for i in range(n)]
    return np.clip(np.rint(q), spec["min"], spec["max"]).astype(np.int64)


def _zipf_counts(n: int, n_adapters: int, s: float) -> np.ndarray:
    w = 1.0 / np.arange(1, n_adapters + 1) ** s
    share = n * w / w.sum()
    counts = np.floor(share).astype(np.int64)
    rest = np.argsort(-(share - counts), kind="stable")[: n - counts.sum()]
    counts[rest] += 1
    return counts


def generate(spec: Dict, *, rate: float, seconds: float, seed: int,
             vocab: int, n_adapters: int) -> List[Request]:
    n = max(1, int(round(rate * seconds)))
    order = np.random.default_rng(spec.get("schedule_seed", 0))
    rng = np.random.default_rng(seed)
    gaps = np.array([-math.log(1.0 - (i + .5) / n) for i in range(n)])
    # the last arrival falls half a mean gap before the window closes
    gaps *= (seconds - 0.5 / rate) / gaps.sum()
    offsets = np.cumsum(order.permutation(gaps))
    prompts = order.permutation(_lognormal_quantiles(spec["prompt"], n))
    outputs = order.permutation(_lognormal_quantiles(spec["output"], n))
    ranks = np.repeat(np.arange(n_adapters),
                      _zipf_counts(n, n_adapters, spec["zipf_s"]))
    ranks = rng.permutation(ranks)
    adapter_of_rank = rng.permutation(n_adapters)
    return [Request(offset=float(offsets[i]),
                    prompt=rng.integers(0, vocab, int(prompts[i]),
                                        dtype=np.int64).tolist(),
                    output_len=int(outputs[i]),
                    adapter=int(adapter_of_rank[ranks[i]]))
            for i in range(n)]
