"""End-to-end driver: train a ~100M-parameter qwen3-family model.

    PYTHONPATH=src python examples/train_100m.py [--steps 300] [--bicompfl]

Uses the real production stack -- config system, sharded Trainer (pjit on
whatever devices exist; a degenerate 1x1 mesh on this CPU container),
synthetic Markov token pipeline, checkpointing.  ``--bicompfl`` turns on the
paper's stochastic-sign gradient compression inside the train step.

~100M config: 12 layers, d_model 768, 12 heads (GQA kv=4), d_ff 2048,
vocab 8192 => ~98M parameters.
"""
import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro import checkpoint
from repro.data import batches_for
from repro.launch.mesh import make_host_mesh
from repro.launch.train import Trainer
from repro.models.config import ArchConfig
from repro import compile_cache

CFG_100M = ArchConfig(
    name="repro-100m", arch_type="dense",
    n_layers=12, d_model=768, n_heads=12, n_kv_heads=4,
    d_ff=2048, vocab=8192, head_dim=64,
    qk_norm=True, dtype="float32", remat=False,
    source="examples/train_100m.py (qwen3-family, scaled)",
)


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--bicompfl", action="store_true",
                    help="stochastic-sign + MRC-style gradient compression")
    ap.add_argument("--ckpt", default="/tmp/repro_100m.ckpt")
    args = ap.parse_args()

    cfg = CFG_100M
    n = cfg.params_count()
    print(f"arch {cfg.name}: {n/1e6:.0f}M params, vocab {cfg.vocab}")

    trainer = Trainer(cfg, mesh=make_host_mesh(), lr=args.lr,
                      microbatches=1, kv_chunk=args.seq,
                      grad_compression="stochastic_sign" if args.bicompfl else None)

    data = batches_for(cfg, args.batch, args.seq, seed=0)
    t0 = time.time()
    losses = []
    for step, batch in enumerate(data):
        if step >= args.steps:
            break
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        loss = trainer.step(batch)
        losses.append(loss)
        if step % 20 == 0 or step == args.steps - 1:
            dt = time.time() - t0
            tok_s = (step + 1) * args.batch * args.seq / dt
            print(f"step {step:4d}  loss {loss:8.4f}  ({tok_s:,.0f} tok/s)",
                  flush=True)

    assert losses[-1] < losses[0], "loss did not decrease"
    checkpoint.save(args.ckpt, trainer.params, step=args.steps)
    print(f"saved checkpoint to {args.ckpt}")
    print(f"loss {losses[0]:.3f} -> {losses[-1]:.3f} over {args.steps} steps "
          f"in {time.time()-t0:.0f}s")


if __name__ == "__main__":
    main()
