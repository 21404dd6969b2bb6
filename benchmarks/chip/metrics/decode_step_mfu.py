"""Share of the chips' peak that the decode steps' useful FLOPs reach over
their device time: the useful FLOPs (the layout's decode_step_flops, live
rows only) of the decode rounds traced, per step, times the decode-step
runs in the trace, over those runs' device time times the peak."""
from layerctx import DECODE_MODULE

LAYER = "model step"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "tpot_p90_ms"


def read(ctx):
    rounds = ctx.traced_rounds()
    per_chip = [evs for evs in ctx.module_events(DECODE_MODULE) if evs]
    if not rounds or not per_chip:
        return None
    flops = sum(ctx.layout.decode_step_flops(ctx.dims, r.contexts)
                for r in rounds) / len(rounds)
    runs = sum(len(evs) for evs in per_chip) / len(per_chip)
    dev_s = sum(sum(e.dur for e in evs) for evs in per_chip) / len(per_chip)
    return 100.0 * flops * runs / (dev_s * ctx.peak["bf16_flops"]
                                   * ctx.chips)
