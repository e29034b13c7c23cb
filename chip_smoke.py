"""Smoke test of R-FAST asynchronous training on TPU at rfast-100m's full
width, through the training CLI's own entry point.

    python chip_smoke.py             # one chip: --nodes 2
    python chip_smoke.py --chips 4   # four chips: --nodes 4 --param-shards 4

Each phase calls ``repro.launch.train.main`` twice in this one process,
with ``--impl pallas`` (the compiled commit kernel) and with the
``--impl jnp`` reference, from the same seed and random weights.  It
fails unless both runs' eval losses agree within 1e-3, the loss fell, a
sample of the final state agrees, and every commit-kernel dispatch key
is ``compiled``.  The one-chip phase also fails when a single compile
takes more than 60 s; the four-chip phase when the devices' peak
``bytes_in_use`` lie more than 1 GiB apart.  Earlier lines report the
device, the resolved commit mode, the compile cache directory, each
compile's time, the seconds to the first eval and per later chunk, and
the peak ``bytes_in_use`` per device.  The last line is the JSON verdict
``{"ok": true, "device": {...}}``; it is printed only when JAX runs on a
TPU and every check passed, and the exit code is non-zero otherwise.
"""
import argparse
import json
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.launch.xla_env import configure_compile_cache  # noqa: E402

LOSS_TOL = 1e-3          # |loss(pallas) - loss(jnp)| per eval
STATE_TOL = 1e-3         # max |diff| / max |ref| over the state sample
MAX_COMPILE_S = 60.0     # longest single compile on the one-chip path
MAX_PEAK_SPREAD = 2**30  # four chips: max - min peak bytes_in_use

COMMON = ["--arch", "rfast-100m", "--scenario", "straggler",
          "--topology", "binary_tree", "--steps", "2", "--log-every", "1",
          "--seed", "0"]
PHASES = {1: ["--nodes", "2"],
          4: ["--nodes", "4", "--param-shards", "4"]}
SAMPLE = 4096            # columns taken from each end of every state row


class CheckFailed(SystemExit):
    def __init__(self, msg: str):
        super().__init__(f"chip_smoke: FAILED: {msg}")


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise CheckFailed(msg)


def state_sample(state) -> dict[str, np.ndarray]:
    """Host copies of the first and last SAMPLE columns of every array
    of the final state (the rest stays on the device and is dropped)."""
    out = {}
    for name, a in zip(state._fields, state):
        if a.ndim == 0:
            continue
        out[name] = np.concatenate([np.asarray(a[..., :SAMPLE]),
                                    np.asarray(a[..., -SAMPLE:])], axis=-1)
    return out


def peaks(devices) -> list[int]:
    return [int(d.memory_stats()["peak_bytes_in_use"]) for d in devices]


def run(train, impl: str, chips: int, compiles: list, devices) -> dict:
    compiles.clear()
    res = train.main(COMMON + PHASES[chips] + ["--impl", impl])
    wall = res["eval_wall"]
    print(f"[{impl}] commit mode: {res['commit_mode']}")
    print(f"[{impl}] eval losses: {res['losses']}")
    print(f"[{impl}] seconds to the first eval: {wall[0]:.2f}; each later "
          f"chunk: {[round(b - a, 3) for a, b in zip(wall, wall[1:])]}")
    small = [s for _, s in compiles if s < 0.5]
    for name, secs in sorted(compiles, key=lambda c: -c[1]):
        if secs >= 0.5:
            print(f"[{impl}] compile {name}: {secs:.2f}s")
    print(f"[{impl}] {len(small)} more compiles under 0.5s each, "
          f"{sum(small):.2f}s in all")
    print(f"[{impl}] peak bytes_in_use per device so far: {peaks(devices)}")
    res["longest_compile"] = max((s for _, s in compiles), default=0.0)
    res["sample"] = state_sample(res.pop("state"))
    return res


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=sorted(PHASES), default=1,
                    help="1: the one-chip phase (default); 4: the "
                         "--param-shards 4 phase, and nothing else")
    args = ap.parse_args()

    cache_dir = configure_compile_cache()
    import jax
    from jax import monitoring

    from repro.kernels.rfast_update import dispatch
    from repro.launch import train

    devices = jax.devices()
    dev = devices[0]
    print(f"device: {dev.platform} {dev.device_kind} x {len(devices)}")
    print(f"compile cache: {cache_dir}")
    check(dev.platform == "tpu",
          f"JAX found no TPU (platform {dev.platform!r})")
    check(len(devices) >= args.chips,
          f"{args.chips} chips asked for, {len(devices)} found")

    # every backend compile (a persistent-cache hit included) reports its
    # duration under this event, with the jitted function's name
    compiles: list[tuple[str, float]] = []

    def on_duration(event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append((str(kw.get("fun_name", "?")), secs))

    monitoring.register_event_duration_secs_listener(on_duration)

    pal = run(train, "pallas", args.chips, compiles, devices)
    ref = run(train, "jnp", args.chips, compiles, devices)

    check(pal["commit_mode"] == "compiled",
          f"pallas commit ran as {pal['commit_mode']!r}, not compiled")
    keys = dispatch.keys()
    print(f"commit_grid dispatch keys (kernel, mode): "
          f"{sorted({k[:2] for k in keys})}")
    check(bool(keys) and all(k[1] == "compiled" for k in keys),
          f"dispatch keys not all compiled: {keys}")
    lp, lr = np.asarray(pal["losses"]), np.asarray(ref["losses"])
    check(lp.shape == lr.shape and lp.size > 1,
          f"eval counts differ or too few: {lp.size} vs {lr.size}")
    diff = float(np.max(np.abs(lp - lr)))
    print(f"max |loss(pallas) - loss(jnp)| = {diff:.3e} (tol {LOSS_TOL})")
    check(diff <= LOSS_TOL, "pallas and jnp eval losses disagree")
    check(bool(np.all(np.isfinite(lp))), "non-finite loss")
    print(f"loss {lr[0]:.4f} -> {lr[-1]:.4f}")
    check(lp[-1] < lp[0] and lr[-1] < lr[0], "the loss did not fall")
    for name, want in ref["sample"].items():
        got = pal["sample"][name]
        check(got.shape == want.shape and np.all(np.isfinite(got)),
              f"state {name}: shape {got.shape} vs {want.shape} or "
              "non-finite values")
        scale = max(float(np.max(np.abs(want))), 1e-30)
        rel = float(np.max(np.abs(got - want))) / scale
        print(f"final state {name} sample {got.shape}: max |diff| / "
              f"max |ref| = {rel:.3e} (tol {STATE_TOL})")
        check(rel <= STATE_TOL, f"final state {name} disagrees")

    peak = peaks(devices[:args.chips])
    print(f"peak bytes_in_use per device: {peak}")
    if args.chips == 1:
        longest = max(pal["longest_compile"], ref["longest_compile"])
        print(f"longest single compile: {longest:.2f}s "
              f"(limit {MAX_COMPILE_S:.0f}s)")
        check(longest <= MAX_COMPILE_S, "a compile took over 60 s")
    else:
        spread = max(peak) - min(peak)
        print(f"peak spread across devices: {spread / 2**30:.3f} GiB "
              f"(limit {MAX_PEAK_SPREAD / 2**30:.0f} GiB)")
        check(spread <= MAX_PEAK_SPREAD,
              "device peaks lie more than 1 GiB apart")

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
