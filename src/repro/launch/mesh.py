"""Production meshes.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state.  The dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import to obtain the placeholder devices.
"""
from __future__ import annotations

import jax
import numpy as np

__all__ = ["make_production_mesh", "make_sweep_mesh", "node_axes_for",
           "PEAKS", "DRYRUN_DEVICE_KIND", "peak_rates"]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    # GSPMD (Auto) axes: the specs place arrays by sharding rules, not
    # by sharding-in-types
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_sweep_mesh(*, lanes: int | None = None, param_shards: int = 1,
                    devices=None, lane_axis: str = "data",
                    param_axis: str = "model"):
    """(lane-groups × param-shards) mesh for the mesh-mapped fleet sweep
    (``repro.core.simulator.run_sweep(mesh=...)``).

    Uses however many devices the backend exposes — real accelerators or
    the CPU dev loop's forced host devices
    (:func:`repro.launch.xla_env.force_host_devices` /
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``, which must be
    set before jax initializes its backends).  Defaults: all devices on
    the lane axis, no parameter sharding.  Unlike
    :func:`make_production_mesh` this never *requires* a device count —
    any ``lanes * param_shards <= len(devices)`` prefix works, so the
    same call runs on 1-device CI and a 256-chip pod.
    """
    devices = list(devices) if devices is not None else jax.devices()
    m = int(param_shards)
    if m < 1:
        raise ValueError(f"param_shards must be >= 1, got {m}")
    d = int(lanes) if lanes is not None else max(1, len(devices) // m)
    if d < 1:
        raise ValueError(f"lanes must be >= 1, got {d}")
    if d * m > len(devices):
        raise ValueError(f"mesh {d}x{m} needs {d * m} devices, have "
                         f"{len(devices)} (force more host devices via "
                         "repro.launch.xla_env.force_host_devices)")
    arr = np.array(devices[:d * m]).reshape(d, m)
    return jax.sharding.Mesh(arr, (lane_axis, param_axis))


def node_axes_for(mesh, *, n_nodes: int | None = None) -> tuple[str, ...]:
    """Which mesh axes carry the R-FAST node dimension.

    Default: all non-'model' axes (16 nodes single-pod, 32 multi-pod).
    ``n_nodes`` may select the 'pod'-only variant (nodes span pods, the
    'data' axis is then free for FSDP) — used by the memory hillclimb.
    """
    names = mesh.axis_names
    if n_nodes is None:
        return tuple(a for a in names if a != "model")
    if "pod" in names and n_nodes == mesh.shape["pod"]:
        return ("pod",)
    non_model = tuple(a for a in names if a != "model")
    prod = 1
    for a in non_model:
        prod *= mesh.shape[a]
    if n_nodes == prod:
        return non_model
    raise ValueError(f"unsupported n_nodes={n_nodes} for mesh {names}")


# Peak rates of one chip, keyed by jax's ``Device.device_kind``.
# "TPU v5 lite" is the TPU v5e; source: Google Cloud documentation,
# "TPU v5e" (system architecture): 197 TFLOP/s bf16, 819 GB/s of HBM
# bandwidth, and 1,600 Gbit/s of chip-to-chip interconnect over four ICI
# links, i.e. 50 GB/s per link.
PEAKS = {
    "TPU v5 lite": {
        "peak_flops_bf16": 197e12,   # FLOP/s
        "hbm_bw": 819e9,             # B/s
        "ici_bw": 50e9,              # B/s per link
    },
}

# the chip the production dry-run meshes (make_production_mesh) model
DRYRUN_DEVICE_KIND = "TPU v5 lite"


def peak_rates(device_kind: str) -> dict:
    """The :data:`PEAKS` entry of ``device_kind``; an unknown kind is an
    error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no peak rates for device kind {device_kind!r}; "
                         f"known: {sorted(PEAKS)}") from None
