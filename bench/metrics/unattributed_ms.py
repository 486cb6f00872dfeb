"""Device milliseconds per round outside every round-stage scope: the
union of the device's busy intervals less the union of the ops under any
``fl.*`` stage scope (``harness.program_trace.STAGES``).  What is left is
the scan's own bookkeeping and the small programs the host runs between
calls; it says how far the stage metrics cover the round."""
from harness.program_trace import unattributed

UNIT = "ms"


def read(ctx):
    secs, n = unattributed(ctx.trace)
    return secs * 1e3 / ctx.rounds if n else None
