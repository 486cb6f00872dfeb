"""Example: lower one (arch x shape) pair on the production mesh and print
its roofline decomposition -- the programmatic version of
``python -m repro.launch.dryrun``.

    PYTHONPATH=src python examples/multi_arch_dryrun.py --arch jamba-v0.1-52b \
        --shape decode_32k [--multi-pod]
"""
import argparse
import json

# must run before any other jax-touching import (device-count lock-in)
from repro.launch import dryrun  # noqa: F401  (sets XLA_FLAGS at import)
from repro import compile_cache


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="jamba-v0.1-52b")
    ap.add_argument("--shape", default="decode_32k")
    ap.add_argument("--multi-pod", action="store_true")
    args = ap.parse_args()

    res = dryrun.run_combo(args.arch, args.shape, multi_pod=args.multi_pod)
    print(json.dumps(res, indent=2, default=str))


if __name__ == "__main__":
    main()
