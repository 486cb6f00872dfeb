"""Device milliseconds per round of the uplink stage: the union of the ops
under the program's ``fl.uplink`` scope (``FLEngine._round_core``: the
channel's ``step_up``, its MRC encode included, and its pin)."""
from harness.program_trace import ms_per_round

UNIT = "ms"
KEYS = ("fl.uplink",)


def read(ctx):
    return ms_per_round(ctx, KEYS)
