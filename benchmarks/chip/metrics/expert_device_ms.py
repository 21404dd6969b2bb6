"""Mean device time of one decode-step run spent in the base experts:
its operations whose innermost named scope is ``moe_experts`` (the
gate, up and down expert GEMMs of every layer and the activation
between them), joined to the program's scopes through the HLO the
profile keeps (scopes.py), averaged over the runs and the chips.
Nothing to read without the named scopes."""
import scopes

LAYER = "model step"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "tpot_p90_ms"


def read(ctx):
    split = scopes.split_of(ctx)
    return None if split is None else 1e3 * split.get("moe_experts", 0.0)
