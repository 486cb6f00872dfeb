"""Device milliseconds per round of MRC candidate generation: the union of
the ops under the ``mrc.draw`` scope (``repro/core/mrc.py``: the threefry
candidates, their compare with the prior and the f32 0/1 copy, in both
directions; the decoders' regeneration of the selected row)."""
from harness.program_trace import ms_per_round

UNIT = "ms"
KEYS = ("mrc.draw",)


def read(ctx):
    return ms_per_round(ctx, KEYS)
