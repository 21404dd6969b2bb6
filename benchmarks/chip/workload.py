"""Traffic: one data file per mix (``traffic/<name>.json``) read by the
generator its ``kind`` names (``generators/<kind>.py``, a ``generate``
function). A new mix of an existing kind is a new data file only."""
from __future__ import annotations

import dataclasses
import json
from typing import Dict, List

from spec import CHIP_DIR, load_module


@dataclasses.dataclass
class Request:
    offset: float           # scheduled send time, seconds after window start
    prompt: List[int]
    output_len: int
    adapter: int


def load_traffic(name: str) -> Dict:
    with open(CHIP_DIR / "traffic" / f"{name}.json") as f:
        return json.load(f)


def _generator(kind: str):
    return load_module(f"generators/{kind}.py").generate


def generate(traffic: Dict, *, rate: float, seconds: float, seed: int,
             vocab: int, n_adapters: int) -> List[Request]:
    """The window's requests, sorted by scheduled send time."""
    reqs = _generator(traffic["kind"])(traffic, rate=rate, seconds=seconds,
                                       seed=seed, vocab=vocab,
                                       n_adapters=n_adapters)
    return sorted(reqs, key=lambda r: r.offset)


def longest_request(traffic: Dict) -> Dict[str, int]:
    """The largest prompt and output the mix can produce."""
    return {"prompt": int(traffic["prompt"]["max"]),
            "output": int(traffic["output"]["max"])}
