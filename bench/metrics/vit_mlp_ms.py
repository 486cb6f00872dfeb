"""Device milliseconds per round of the ViT's MLP blocks: the union of the
ops under the ``vit.mlp`` scope (``repro/fl/nets.py`` ``make_vit``: the
pre-MLP LayerNorm, fc1, GELU, fc2 and the residual add), forward and
transpose, in local training and in evaluation.  None for a net without
the scope."""
from harness.program_trace import ms_per_round

UNIT = "ms"
KEYS = ("vit.mlp",)


def read(ctx):
    return ms_per_round(ctx, KEYS)
