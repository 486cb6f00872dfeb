"""Device milliseconds per round of the ViT's attention: the union of the
ops under the ``vit.attn`` scope (``repro/fl/nets.py`` ``make_vit``: the
pre-attention LayerNorm, the q, k, v and output projections, the logits,
softmax and weighted sum, and the residual add), forward and transpose,
in local training and in evaluation.  None for a net without the scope."""
from harness.program_trace import ms_per_round

UNIT = "ms"
KEYS = ("vit.attn",)


def read(ctx):
    return ms_per_round(ctx, KEYS)
