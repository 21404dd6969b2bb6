"""Mean wall time of a decode round that admitted nothing: one call of
ServeSystem.step, host scheduling and the decode step together (host
clock, averaged over all such rounds of the window)."""
LAYER = "engine"
UNIT = "ms"
SOURCE = "host_clock"
MOVES = "tpot_p90_ms"


def read(ctx):
    rec = ctx.record
    ds = [r.end - r.start for r in rec.rounds
          if r.contexts and r.admitted == 0 and r.end <= rec.t_end]
    return 1e3 * sum(ds) / len(ds) if ds else None
