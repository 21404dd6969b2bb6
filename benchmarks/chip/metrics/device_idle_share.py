"""Share of the traced slice in which no operation ran on the device:
1 - (union of the device's operation intervals / the slice's length),
averaged over the chips."""
import trace_reduce

LAYER = "device"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "tpot_p90_ms"


def read(ctx):
    span = ctx.record.trace_span
    lines = ctx.device_lines()
    if not lines or span is None or span[1] is None:
        return None
    window = span[1] - span[0]
    busy = sum(trace_reduce.busy_seconds(ln) for ln in lines) / len(lines)
    return 100.0 * (1.0 - busy / window)
