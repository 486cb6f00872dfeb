"""Share of its compute roofline that the ViT's attention reaches: the
attention's model FLOPs a round (``bench/flops/<builder>.py``
``attn_flops``: the q, k, v and output projections, Q K^T and P V), over
the device time under ``vit.attn`` (``vit_attn_ms``) times the chip's bf16
peak.  Local training counts each step's forward three times (forward and
a backward of twice it); evaluation counts one forward of the test set
every ``eval_every`` rounds.  None for a net without the scope."""
from harness.cell import load_module
from harness.program_trace import time_union

UNIT = "%"
KEYS = ("vit.attn",)


def read(ctx):
    if ctx.peaks is None:
        return None
    secs, n = time_union(ctx.trace, KEYS)
    if not n or secs <= 0:
        return None
    c = ctx.cell
    flops = load_module("flops", f"{c.net['builder']}.py")
    if not hasattr(flops, "attn_flops"):
        return None
    f = float(flops.attn_flops(**c.net["args"]))
    dep, t = c.traffic["deployment"], c.task
    per = int(dep["per_client"])
    bs = min(int(t["batch_size"]), per)
    steps = int(t["local_epochs"]) * max(per // bs, 1)
    n_active = max(1, round(float(dep["participation"]) * c.n_clients))
    per_round = (n_active * steps * bs * 3 * f
                 + int(c.config["data"]["n_test"]) * f / c.eval_every)
    achieved = per_round * ctx.rounds / secs
    return 100.0 * achieved / ctx.peaks["bf16_flops_per_s"]
