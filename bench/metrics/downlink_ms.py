"""Device milliseconds per round of the downlink stage: the union of the
ops under the program's ``fl.downlink`` scope (``FLEngine._round_core``:
the channel's ``step_down``, the PR downlink's per-client MRC encode
included, and its pin)."""
from harness.program_trace import ms_per_round

UNIT = "ms"
KEYS = ("fl.downlink",)


def read(ctx):
    return ms_per_round(ctx, KEYS)
