"""Device milliseconds per round of the MRC codec (``repro/core/mrc.py``):
the ops under the fixed-block and segment encoders and decoders."""

UNIT = "ms"
KEYS = ("encode_fixed", "decode_fixed", "_encode_segments",
        "_decode_segments")


def read(ctx):
    secs, n = ctx.trace.time_under(KEYS)
    return secs * 1e3 / ctx.rounds if n else None
