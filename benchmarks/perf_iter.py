"""§Perf hillclimb driver: lower one (arch, shape) with config/mb
overrides and print the roofline delta vs. the recorded baseline.

    PYTHONPATH=src python -m benchmarks.perf_iter --arch rwkv6-1.6b \
        --shape train_4k --set scan_chunk=64 --mb 4
"""
import argparse
import json

from repro.launch import dryrun  # noqa: F401  (sets XLA_FLAGS first)
from repro import compile_cache


def parse_overrides(items):
    out = {}
    for it in items or ():
        k, v = it.split("=", 1)
        try:
            out[k] = int(v)
        except ValueError:
            try:
                out[k] = float(v)
            except ValueError:
                out[k] = v
    return out


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--set", action="append", default=[],
                    help="ArchConfig overrides, e.g. scan_chunk=64")
    ap.add_argument("--mb", type=int, default=None, help="microbatches")
    ap.add_argument("--kv-chunk", type=int, default=1024)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--json", default=None)
    args = ap.parse_args()

    res = dryrun.run_combo(args.arch, args.shape, multi_pod=args.multi_pod,
                           kv_chunk=args.kv_chunk,
                           overrides=parse_overrides(args.set),
                           microbatches=args.mb)
    rl = res.get("roofline", {})
    print(json.dumps({k: res[k] for k in
                      ("arch", "shape", "status", "compile_s") if k in res}))
    if rl:
        print(f"compute_s    {rl['compute_s']:.4f}")
        print(f"memory_s     {rl['memory_s']:.4f}")
        print(f"collective_s {rl['collective_s']:.4f}")
        print(f"dominant     {rl['dominant']}   bound {rl['bound_s']:.4f}")
        print(f"flops/dev {rl['flops_per_dev']:.3e}  "
              f"hbm/dev {rl['hbm_bytes_per_dev']:.3e}  "
              f"coll/dev {rl['coll_bytes_per_dev']:.3e}")
        print("collectives:", res["collectives"])
        print("memory:", {k: v for k, v in res["memory"].items()})
    if args.json:
        with open(args.json, "w") as f:
            json.dump(res, f, indent=1, default=str)


if __name__ == "__main__":
    main()
