"""Device milliseconds per round of the local-training batch fetch: the
union of the ops under the ``local.batch`` scope (``repro/fl/tasks.py``:
``xs[idx], ys[idx]`` of every local step)."""
from harness.program_trace import ms_per_round

UNIT = "ms"
KEYS = ("local.batch",)


def read(ctx):
    return ms_per_round(ctx, KEYS)
