"""Device milliseconds per round of the MRC importance weights: the union
of the ops under the ``mrc.logw`` scope (``repro/core/mrc.py``: the
log-ratio coefficients and the ``logw_fn``/``seg_logw_fn`` call, in both
directions)."""
from harness.program_trace import ms_per_round

UNIT = "ms"
KEYS = ("mrc.logw",)


def read(ctx):
    return ms_per_round(ctx, KEYS)
