"""§Perf hillclimb driver: run named dry-run variants for the three chosen
(arch × shape) pairs and print their roofline terms side by side.

    PYTHONPATH=src python -m repro.launch.hillclimb --pair train|moe|decode
"""
import os

# must run before jax initializes; appends to the operator's own
# XLA_FLAGS (e.g. dump directives survive, an explicit device count wins)
from repro.launch.xla_env import force_host_devices
force_host_devices()

import argparse
import json

from repro.launch.dryrun import run_case
from repro.launch.mesh import DRYRUN_DEVICE_KIND, peak_rates


def terms(rec: dict) -> str:
    if not rec.get("ok"):
        return f"FAILED: {rec.get('error', '')[:160]}"
    fit = rec.get("fit")
    if fit:
        fl, by, co = (fit["flops_perdev"], fit["bytes_perdev"],
                      fit["coll_bytes_perdev"])
    else:
        fl, by = rec["cost_scanned"]["flops"], rec["cost_scanned"]["bytes"]
        co = sum(v["bytes"]
                 for v in rec.get("collectives_scanned", {}).values())
    mem = rec["memory"]
    hw = peak_rates(DRYRUN_DEVICE_KIND)
    return (f"compute={fl/hw['peak_flops_bf16']:.3f}s "
            f"memory={by/hw['hbm_bw']:.3f}s "
            f"collective={co/hw['ici_bw']:.3f}s "
            f"args={mem['argument_size_in_bytes']/2**30:.1f}GiB "
            f"temp={mem['temp_size_in_bytes']/2**30:.1f}GiB")


VARIANTS = {
    "train": [  # llama3-8b x train_4k (paper-representative)
        ("it0_dense_fullce", "llama3-8b", "train_4k",
         dict(), "base", dict(comm="dense", ce="full")),
        ("it1_ppermute_fullce", "llama3-8b", "train_4k",
         dict(), "base", dict(comm="ppermute", ce="full")),
        ("it2_ppermute_lsece", "llama3-8b", "train_4k",
         dict(), "base", dict(comm="ppermute", ce="lse")),
    ],
    "moe": [   # deepseek-v2-236b x train_4k (worst memory / does not fit)
        ("it0_nodes32_base", "deepseek-v2-236b", "train_4k",
         dict(multi_pod=True), "base", dict()),
        ("it1_nodepod_fsdp", "deepseek-v2-236b", "train_4k",
         dict(multi_pod=True), "fsdp", dict(node_axes=("pod",))),
    ],
    "decode": [  # llama3-8b x decode_32k (most collective-bound)
        ("it0_headdim_cache", "llama3-8b", "decode_32k",
         dict(), "base", dict()),
        ("it1_seqshard_cache", "llama3-8b", "decode_32k",
         dict(), "base", dict(cache_seq_shard=True)),
    ],
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--pair", choices=list(VARIANTS) + ["all"],
                    default="all")
    ap.add_argument("--out", default="reports/hillclimb")
    ap.add_argument("--no-fit", action="store_true")
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)

    pairs = list(VARIANTS) if args.pair == "all" else [args.pair]
    for pair in pairs:
        print(f"=== {pair} ===", flush=True)
        for name, arch, shape, case_kw, rules, build_kw in VARIANTS[pair]:
            rec = run_case(arch, shape, rules_name=rules,
                           fit=not args.no_fit, build_kw=build_kw,
                           verbose=False, **case_kw)
            rec["variant"] = name
            with open(os.path.join(args.out, f"{pair}__{name}.json"),
                      "w") as f:
                json.dump(rec, f, indent=1)
            print(f"{name:24s} {terms(rec)}", flush=True)


if __name__ == "__main__":
    main()
