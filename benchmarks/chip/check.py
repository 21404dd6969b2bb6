"""What decides ``correct``: the served tokens against the plain reference.

Once the window has closed and the program's state is freed, a sample of
the finished requests, drawn from the seed and holding the longest one,
is run through the configuration's reference (the module its
``reference`` key names, float32) on the weights as drawn and placed,
over each prompt followed by its served tokens. For each served token
the gap is the reference's best logit at that position minus the
reference's logit of the served token (0 where the served token is the
reference's choice). The number compared is the 98th percentile of
those gaps over the sample (``p98_logit_gap``). The widest gap is
printed beside it and not compared: in bf16 a top-k router near a tie
sends a single token to another expert now and then, which moves that
token's logits by as much as the fp8 control moves them, so the widest
gaps of sound runs and of the control overlap (PERF.md, Findings). Sound
runs hold 0 to 5 such tokens in a sample of 400 or more, and fewer than
2.2 % of their tokens off the reference's top at all; the control puts
10 to 18 % off it. The 98th percentile leaves out the few and reads the
many.

The control computes the same reference with fp8 operands (the reference
module's ``quant="fp8"``) and reads, at the same positions, the gap of
the token that the control puts first.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

import spec
from harness import Built, Record, Sent
from e2e import done

SAMPLE_TOKENS = 400


def sample(rec: Record, seed: int, min_tokens: int = SAMPLE_TOKENS
           ) -> List[Sent]:
    """The longest finished request, then others in an order drawn from
    the seed, until the sample holds ``min_tokens`` served tokens."""
    fin = [s for s in rec.sent if done(s)]
    if not fin:
        return []
    fin.sort(key=lambda s: -len(s.tokens))
    rest = fin[1:]
    order = np.random.default_rng(seed + 1).permutation(len(rest))
    out, n = [fin[0]], len(fin[0].tokens)
    for i in order:
        if n >= min_tokens:
            break
        out.append(rest[i])
        n += len(rest[i].tokens)
    return out


def gaps(b: Built, picked: List[Sent], pad_to: int,
         control: bool = False) -> Dict[str, np.ndarray]:
    """Per served position: the program's gap and, with ``control``, the
    control's gap and the control's logits' top token."""
    ref = spec.load_module(b.conf["reference"])
    max_out = b.longest["output"]
    served, ctl = [], []
    for s in picked:
        n = len(s.tokens)
        seq = np.asarray(list(s.req.prompt) + s.tokens[:-1], np.int64)
        rows = np.full(max_out, s.plen - 1 + n - 1, np.int64)
        rows[:n] = np.arange(s.plen - 1, s.plen - 1 + n)
        args = (b.params, b.adapters, b.dims, seq, s.plen - 1,
                s.req.adapter, rows)
        lg = ref.logits(*args, pad_to=pad_to)[:n]
        best = lg.max(-1)
        served.append(best - lg[np.arange(n), np.asarray(s.tokens)])
        if control:
            top = ref.logits(*args, pad_to=pad_to, quant="fp8")[:n].argmax(-1)
            ctl.append(best - lg[np.arange(n), top])
    out = {"served": np.concatenate(served) if served else np.zeros(0)}
    if control:
        out["control"] = np.concatenate(ctl)
    return out


def p98(g: np.ndarray) -> float:
    return float(np.percentile(g, 98))


def stats(g: np.ndarray) -> Dict[str, float]:
    if g.size == 0:
        return {"max": float("nan"), "p99": float("nan"),
                "p98": float("nan"), "p95": float("nan"),
                "mean": float("nan"), "off_top": float("nan"),
                "over_0.1": 0}
    return {"max": float(g.max()), "p99": float(np.percentile(g, 99)),
            "p98": p98(g),
            "p95": float(np.percentile(g, 95)), "mean": float(g.mean()),
            "off_top": float(np.mean(g > 0)), "over_0.1": int(np.sum(g > 0.1))}


def decide(rec: Record, served_gaps: Optional[np.ndarray], limits: Dict
           ) -> Dict:
    """The numbers compared, each with its limit, and the verdict."""
    sent = len(rec.sent)
    finished = sum(1 for s in rec.sent if done(s))
    checks = {"requests_finished": {"value": finished, "limit": sent,
                                    "rule": "=="}}
    ok = finished == sent
    limit = limits["p98_logit_gap"]
    if served_gaps is None or served_gaps.size == 0:
        ok = False
        checks["p98_logit_gap"] = {"value": None, "limit": limit,
                                   "rule": "<="}
    else:
        v = p98(served_gaps)
        checks["p98_logit_gap"] = {"value": v, "limit": limit, "rule": "<="}
        ok = ok and v <= limit
    # at least SAMPLE_TOKENS served tokens compared, or all there were
    want = min(SAMPLE_TOKENS, sum(len(s.tokens) for s in rec.sent if done(s)))
    n = int(0 if served_gaps is None else served_gaps.size)
    checks["served_tokens_compared"] = {"value": n, "limit": want,
                                        "rule": ">="}
    ok = ok and n >= want
    return {"correct": bool(ok), "checks": checks}
