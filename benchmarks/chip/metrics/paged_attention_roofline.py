"""Share of its roofline that the Mosaic paged-attention kernel reaches:
the least time the chip needs for the kernel's useful bytes and FLOPs
(the layout's paged_attention_work: each live row's real context) over
the kernel's device time. The work per call is the mean over the decode
rounds traced; there is one call per layer per decode step."""
from layerctx import PAGED_KERNEL
from peaks import roofline_seconds

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "tpot_p90_ms"


def read(ctx):
    rounds = ctx.traced_rounds()
    per_chip = [evs for evs in ctx.op_events(PAGED_KERNEL) if evs]
    if not rounds or not per_chip:
        return None
    least = sum(roofline_seconds(*ctx.layout.paged_attention_work(
        ctx.dims, r.contexts), ctx.peak) for r in rounds) / len(rounds)
    calls = sum(len(evs) for evs in per_chip) / len(per_chip)
    dev_s = sum(sum(e.dur for e in evs) for evs in per_chip) / len(per_chip)
    return 100.0 * least * calls / dev_s
