"""Device milliseconds per round of evaluation: the union of the ops under
the program's ``fl.eval`` scope (the fused scan's eval ``cond``: the test
set's forward pass on the rounds of the eval schedule)."""
from harness.program_trace import ms_per_round

UNIT = "ms"
KEYS = ("fl.eval",)


def read(ctx):
    return ms_per_round(ctx, KEYS)
