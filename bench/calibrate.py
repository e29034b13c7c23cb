"""Readings of the check for setting a cell's limits, in one process:
the program on many seeds, the control (the program's own bfloat16 path,
``harness.run(leaf_dtype="bfloat16")``), and the planted faults
(``faults.py``), each compared with the plain reference over the warm-up
chunk.  No measured window is run.

    python bench/calibrate.py --workload <cell> --seeds 12 [--control 3]
        [--fault half_batch:3 ...] [--out readings.jsonl]

Prints one JSON line per run: ``{"kind", "seed", "correct", "readings"}``.
Needs a TPU, like ``run.py``.
"""
import time

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

FIRST_SEED = 3_000_000_017      # calibration seeds: FIRST_SEED + 7919 i


def readings(workload: str, seeds: int, control: int = 0, faults=(),
             root=None):
    """Yield one record per run: the program on ``seeds`` seeds, the
    control on the first ``control`` of them, and each ``(fault name,
    count)`` on the first ``count``."""
    from bench import faults as fault_mod
    from bench import harness, spec
    root = root or spec.BENCH_DIR
    seed_list = [FIRST_SEED + 7919 * i for i in range(seeds)]
    plan = [("program", s) for s in seed_list]
    plan += [("control", s) for s in seed_list[:control]]
    plan += [(name, s) for name, count in faults
             for s in seed_list[:count]]
    for kind, seed in plan:
        t0 = time.perf_counter()
        if kind in ("program", "control"):
            leaf = "bfloat16" if kind == "control" else "float32"
            res = harness.run(workload, seed, 0, False, t_start=t0,
                              root=root, leaf_dtype=leaf)
        else:
            with fault_mod.FAULTS[kind]():
                res = harness.run(workload, seed, 0, False, t_start=t0,
                                  root=root)
        yield {"workload": workload, "kind": kind, "seed": seed,
               "correct": res["correct"], "readings": res["readings"],
               "seconds": time.perf_counter() - t0}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control", type=int, default=0)
    ap.add_argument("--fault", action="append", default=[],
                    help="NAME:SEEDS")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    import jax
    from bench import spec
    cell = spec.load_cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print("calibrate: needs a TPU", file=sys.stderr)
        return 2
    faults = [(name, int(count)) for name, count in
              (item.split(":") for item in args.fault)]
    out = open(args.out, "a") if args.out else None
    for rec in readings(args.workload, args.seeds, args.control, faults):
        line = json.dumps(rec)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
