"""Batched serving example: prefill-free decode with a KV cache.

    PYTHONPATH=src python examples/serve_decode.py [--arch qwen3-1.7b]

Instantiates the *reduced* variant of an assigned architecture (CPU-sized)
and serves a batch of randomly tokenized requests through the same
``serve_step`` the multi-pod dry-run lowers at full scale.
"""
import argparse
import time

import numpy as np

import repro.configs as C
from repro.launch.serve import Request, Server
from repro import compile_cache


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b", choices=list(C.ALIASES))
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--new-tokens", type=int, default=24)
    args = ap.parse_args()

    cfg = C.get(args.arch).reduced()
    if not cfg.supports_decode:
        raise SystemExit(f"{args.arch} is encoder-only: no decode step")
    print(f"serving reduced {cfg.name}: {cfg.n_layers}L d={cfg.d_model} "
          f"vocab={cfg.vocab}")

    server = Server(cfg, max_batch=args.batch, max_seq=128)
    rng = np.random.default_rng(0)
    reqs = [Request(prompt=rng.integers(0, cfg.vocab, size=rng.integers(4, 12)),
                    max_new_tokens=args.new_tokens, temperature=0.8)
            for _ in range(args.batch)]

    t0 = time.time()
    outs = server.generate(reqs)
    dt = time.time() - t0
    total_new = sum(len(o) for o in outs)
    for i, o in enumerate(outs):
        print(f"req {i}: prompt_len={len(reqs[i].prompt)}  -> {o[:12]}...")
    print(f"{total_new} tokens in {dt:.1f}s  ({total_new/dt:.1f} tok/s, "
          f"CPU, reduced config)")


if __name__ == "__main__":
    main()
