"""Model FLOP utilization of the whole round: the model FLOPs a round
needs (each client's local steps, forward and backward, and the evaluation
forward over the test set every ``eval_every`` rounds; no MRC work),
times the rounds per second of the traced window, over the chip's bf16
peak.  Backward counts twice the forward."""

UNIT = "%"


def read(ctx):
    if ctx.peaks is None or ctx.window_s <= 0:
        return None
    c = ctx.cell
    dep, t = c.traffic["deployment"], c.task
    per, bs = int(dep["per_client"]), int(t["batch_size"])
    bs = min(bs, per)
    steps = int(t["local_epochs"]) * max(per // bs, 1)
    f = c.flops_per_sample()
    n_active = max(1, round(float(dep["participation"]) * c.n_clients))
    train = n_active * steps * bs * 3 * f
    evals = int(c.config["data"]["n_test"]) * f / c.eval_every
    rate = ctx.rounds / ctx.window_s
    return 100.0 * (train + evals) * rate / ctx.peaks["bf16_flops_per_s"]
