"""End-to-end metrics of one window, from the harness's wall-clock record.

Every statistic is over all requests sent in the window. A request that
was refused or did not finish is a miss of both limits; its time to first
token is taken as the time from its scheduled send to the end of the
drain (a lower bound of the true one), so a tail that reaches it is
visible and finite. The limits are the paper's (section 6.1): time to
first token at most 250 ms and mean time per output token at most 100 ms.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from harness import Record, Sent

TTFT_LIMIT_S = 0.25
TPOT_LIMIT_S = 0.10


def done(s: Sent) -> bool:
    return s.finished and len(s.tokens) == s.req.output_len


def ttft_s(s: Sent, rec: Record) -> float:
    if s.stamps:
        return s.stamps[0] - s.sched
    return rec.t_drained - s.sched


def tpot_s(s: Sent) -> float:
    """Mean time per output token after the first; inf if unfinished."""
    if not done(s):
        return float("inf")
    n = len(s.stamps)
    return (s.stamps[-1] - s.stamps[0]) / (n - 1) if n > 1 else 0.0


def p90(xs: List[float]) -> float:
    return float(np.percentile(np.asarray(xs, np.float64), 90))


def metrics(rec: Record, setup_s: float) -> Dict[str, float]:
    sent = rec.sent
    ttft = [ttft_s(s, rec) for s in sent]
    tpot = [tpot_s(s) for s in sent]
    finite_tpot = [t if np.isfinite(t) else rec.t_drained - s.sched
                   for t, s in zip(tpot, sent)]
    ok = sum(1 for s, a, b in zip(sent, ttft, tpot)
             if done(s) and a <= TTFT_LIMIT_S and b <= TPOT_LIMIT_S)
    window = rec.t_end - rec.t0
    tokens = sum(1 for s in sent for t in s.stamps if t <= rec.t_end)
    return {
        "ttft_p90_ms": p90(ttft) * 1e3,
        "tpot_p90_ms": p90(finite_tpot) * 1e3,
        "output_tokens_per_s": tokens / window,
        "slo_attainment": 100.0 * ok / len(sent),
        "setup_s": setup_s,
    }


def generator_lateness_ms(rec: Record) -> Dict[str, float]:
    late = np.asarray([s.submitted - s.sched for s in rec.sent
                       if s.submitted >= 0]) * 1e3
    return {"p50": float(np.median(late)), "p90": float(np.percentile(late, 90)),
            "max": float(late.max())}
