"""90th percentile of the wait from a request's scheduled send time to the
start of the round that admitted it (host clock). A request never
admitted waits until the end of the drain."""
import numpy as np

LAYER = "front door and scheduler"
UNIT = "ms"
SOURCE = "host_clock"
MOVES = "ttft_p90_ms"


def read(ctx):
    rec = ctx.record
    waits = [(s.admitted if s.admitted >= 0 else rec.t_drained) - s.sched
             for s in rec.sent]
    if not waits:
        return None
    return float(np.percentile(waits, 90)) * 1e3
