"""Roofline analysis over the dry-run artifacts.

For every (arch × shape × mesh) JSON produced by dryrun.py, derive:

  compute term    = HLO_FLOPs_perdev / peak_FLOP/s          [s]
  memory term     = HLO_bytes_perdev / HBM_bw               [s]
  collective term = collective_bytes_perdev / ICI_link_bw   [s]

HLO_FLOPs/bytes come from the exact linear-in-L fit (dryrun.py §fit);
SSM/hybrid architectures get a documented analytic correction for the
selective-scan while-loop (its body is counted once per layer by XLA's
cost analysis regardless of sequence length).

Also reports MODEL_FLOPS (6·N_active·tokens for training, 2·N_active·tokens
for inference), the MODEL/HLO usefulness ratio, the HBM-fit verdict
(args+temp vs 16 GiB v5e), the dominant term, and a one-line lever.

    PYTHONPATH=src python -m repro.launch.roofline --reports reports/dryrun \
        --out reports/roofline.md
"""
from __future__ import annotations

import argparse
import glob
import json
import os

from repro.configs import get_config
from repro.launch.mesh import DRYRUN_DEVICE_KIND, peak_rates
from repro.launch.specs import SHAPES

HBM_PER_CHIP = 16 * 2**30          # v5e


def ssm_correction_flops(cfg, shape: str, kind: str) -> float:
    """Global extra FLOPs for selective-scan bodies (counted once by XLA).

    Per timestep per layer: dA=exp(dt·A), dB·u, state update, C·h ≈
    8·d_inner·d_state FLOPs.  Backward ≈ 2× forward.
    """
    if cfg.mixer not in ("ssm", "hybrid"):
        return 0.0
    info = SHAPES[shape]
    tokens = info["batch"] * (info["seq"] if kind != "decode" else 1)
    if kind == "decode":
        return 0.0                      # decode has no scan
    mult = 3.0 if kind == "train" else 1.0
    return mult * cfg.n_layers * 8.0 * cfg.d_inner * cfg.ssm_state * tokens


def model_flops(cfg, shape: str) -> tuple[float, str]:
    info = SHAPES[shape]
    n_active = cfg.active_param_count()
    if info["kind"] == "train":
        tokens = info["batch"] * info["seq"]
        return 6.0 * n_active * tokens, "6·N_active·tokens"
    if info["kind"] == "prefill":
        tokens = info["batch"] * info["seq"]
        return 2.0 * n_active * tokens, "2·N_active·tokens"
    return 2.0 * n_active * info["batch"], "2·N_active·batch"


def lever(dom: str, rec: dict) -> str:
    if dom == "memory":
        return ("cut HBM traffic: coarser remat policy / fused protocol "
                "update (rfast_update kernel) / bf16 CE chunking")
    if dom == "collective":
        return ("cut gossip+TP bytes: overlap ppermute with compute, "
                "quantize protocol messages, widen tree fan-out")
    return "raise MXU utilization: larger per-chip tiles, fused attention"


def analyze(path: str) -> dict | None:
    with open(path) as f:
        rec = json.load(f)
    if rec.get("skipped"):
        return {"arch": rec["arch"], "shape": rec["shape"],
                "mesh": rec["mesh"], "skipped": rec["skipped"]}
    if not rec.get("ok"):
        return {"arch": rec["arch"], "shape": rec["shape"],
                "mesh": rec["mesh"], "error": rec.get("error", "?")}
    cfg = get_config(rec["arch"])
    chips = rec["chips"]
    kind = SHAPES[rec["shape"]]["kind"]

    fit = rec.get("fit")
    if fit:
        fl_pd = fit["flops_perdev"]
        by_pd = fit["bytes_perdev"]
        co_pd = fit["coll_bytes_perdev"]
    else:
        cs = rec["cost_scanned"]
        fl_pd, by_pd = cs["flops"], cs["bytes"]
        co_pd = sum(v["bytes"]
                    for v in rec.get("collectives_scanned", {}).values())

    ssm_fix = ssm_correction_flops(cfg, rec["shape"], kind) / chips
    fl_pd_corr = fl_pd + ssm_fix

    hw = peak_rates(DRYRUN_DEVICE_KIND)
    compute_s = fl_pd_corr / hw["peak_flops_bf16"]
    memory_s = by_pd / hw["hbm_bw"]
    coll_s = co_pd / hw["ici_bw"]
    terms = {"compute": compute_s, "memory": memory_s, "collective": coll_s}
    dom = max(terms, key=terms.get)

    mf, mf_kind = model_flops(cfg, rec["shape"])
    hlo_global = fl_pd_corr * chips
    mem = rec["memory"]
    hbm_need = mem["argument_size_in_bytes"] + mem["temp_size_in_bytes"]

    return {
        "arch": rec["arch"], "shape": rec["shape"], "mesh": rec["mesh"],
        "rules": rec.get("rules", "base"),
        "chips": chips,
        "compute_s": compute_s, "memory_s": memory_s,
        "collective_s": coll_s, "dominant": dom,
        "model_flops": mf, "model_flops_kind": mf_kind,
        "hlo_flops_global": hlo_global,
        "useful_ratio": mf / hlo_global if hlo_global else 0.0,
        "ssm_corr_perdev": ssm_fix,
        "args_gib": mem["argument_size_in_bytes"] / 2**30,
        "temp_gib": mem["temp_size_in_bytes"] / 2**30,
        "fits_hbm": hbm_need <= HBM_PER_CHIP,
        "lever": lever(dom, rec),
    }


def fmt_s(x: float) -> str:
    if x >= 1:
        return f"{x:.2f}s"
    if x >= 1e-3:
        return f"{x*1e3:.1f}ms"
    return f"{x*1e6:.0f}us"


def to_markdown(rows: list[dict]) -> str:
    out = ["| arch | shape | mesh | compute | memory | collective | "
           "dominant | MODEL/HLO | args GiB | temp GiB | fits |",
           "|---|---|---|---|---|---|---|---|---|---|---|"]
    for r in rows:
        if "skipped" in r:
            out.append(f"| {r['arch']} | {r['shape']} | {r['mesh']} | "
                       f"SKIP: {r['skipped'][:40]}… ||||||||")
            continue
        if "error" in r:
            out.append(f"| {r['arch']} | {r['shape']} | {r['mesh']} | "
                       f"ERROR: {r['error'][:40]} ||||||||")
            continue
        out.append(
            f"| {r['arch']} | {r['shape']} | {r['mesh']} | "
            f"{fmt_s(r['compute_s'])} | {fmt_s(r['memory_s'])} | "
            f"{fmt_s(r['collective_s'])} | **{r['dominant']}** | "
            f"{r['useful_ratio']:.2f} | {r['args_gib']:.1f} | "
            f"{r['temp_gib']:.1f} | {'Y' if r['fits_hbm'] else 'N'} |")
    return "\n".join(out)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--reports", default="reports/dryrun")
    ap.add_argument("--out", default="reports/roofline.md")
    ap.add_argument("--json-out", default="reports/roofline.json")
    args = ap.parse_args()

    rows = []
    for path in sorted(glob.glob(os.path.join(args.reports, "*.json"))):
        r = analyze(path)
        if r:
            rows.append(r)
    rows.sort(key=lambda r: (r["arch"], r["shape"], r["mesh"]))
    md = to_markdown(rows)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        f.write("# Roofline (TPU v5e: 197 TF/s bf16, 819 GB/s HBM, "
                "50 GB/s ICI)\n\n" + md + "\n")
    with open(args.json_out, "w") as f:
        json.dump(rows, f, indent=1)
    print(md)


if __name__ == "__main__":
    main()
