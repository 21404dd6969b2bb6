"""Mean device time of one decode-step run spent in the LoRA hooks: its
operations whose innermost named scope is ``lora_hook`` (the two server
hook computations of every layer, their gathers, scale and split),
joined to the program's scopes through the HLO the profile keeps
(scopes.py), averaged over the runs and the chips. Nothing to read
without the named scopes."""
import scopes

LAYER = "LoRA hooks"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "tpot_p90_ms"


def read(ctx):
    split = scopes.split_of(ctx)
    return None if split is None else 1e3 * split.get("lora_hook", 0.0)
