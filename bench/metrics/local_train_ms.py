"""Device milliseconds per round of the clients' local training: the ops
under the ``local_train`` name stack (``repro/fl/tasks.py``)."""

UNIT = "ms"
KEYS = ("local_train",)


def read(ctx):
    secs, n = ctx.trace.time_under(KEYS)
    return secs * 1e3 / ctx.rounds if n else None
