"""Subprocess helper: exercise the dry-run spec machinery end-to-end on a
(4, 4) host-platform mesh with reduced configs (fast CI proxy for the
512-device production dry-run)."""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"

import jax  # noqa: E402
from jax.sharding import AxisType  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.launch.specs import build_case  # noqa: E402


def main():
    mesh = jax.make_mesh((4, 4), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    for arch in ("llama3-8b", "deepseek-v2-236b", "falcon-mamba-7b",
                 "whisper-large-v3"):
        cfg = get_config(arch).reduced()
        for shape in ("train_4k", "decode_32k"):
            from repro.launch.specs import SHAPES
            info = dict(SHAPES[shape])
            # shrink shapes for CI: seq 256/1k, batch 16
            seq = 256 if shape == "train_4k" else 1024
            fn, args = build_case(
                cfg, mesh, shape, **{})
            # rebuild at reduced scale through the kind-specific builders
            from repro.launch import specs as S
            if info["kind"] == "train":
                fn, args = S.build_train(cfg, mesh, seq=seq, global_batch=16)
            else:
                fn, args = S.build_decode(cfg, mesh, seq=seq,
                                          global_batch=16,
                                          long=info.get("long", False))
            compiled = jax.jit(fn).lower(*args).compile()
            ma = compiled.memory_analysis()
            ca = compiled.cost_analysis()
            assert ma.argument_size_in_bytes > 0
            assert ca.get("flops", 0) > 0
            print(f"OK {arch} {shape} args="
                  f"{ma.argument_size_in_bytes/2**20:.1f}MiB "
                  f"flops={ca['flops']:.3g}", flush=True)
    print("DRYRUN-SMALL-PASS")


if __name__ == "__main__":
    main()
