"""Mean device time of one run of the jitted decode-step program, found
in the trace by its XLA module name, averaged over the chips."""
from layerctx import DECODE_MODULE

LAYER = "model step"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "tpot_p90_ms"


def read(ctx):
    per_chip = [evs for evs in ctx.module_events(DECODE_MODULE) if evs]
    if not per_chip:
        return None
    return 1e3 * sum(sum(e.dur for e in evs) / len(evs)
                     for evs in per_chip) / len(per_chip)
