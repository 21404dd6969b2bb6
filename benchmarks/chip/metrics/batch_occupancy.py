"""Mean live rows per decode round: the requests that received a token in
the round, counted by the harness from the round's events."""
LAYER = "engine"
UNIT = "rows"
SOURCE = "program_counter"
MOVES = "output_tokens_per_s"


def read(ctx):
    rows = [len(r.contexts) for r in ctx.record.rounds if r.contexts]
    return sum(rows) / len(rows) if rows else None
