"""The check decides ``correct`` by the plain reference: a sound run of
the program passes, and its bfloat16 control and each planted fault
(``faults.py``) fail.  Each group runs at a test size on the CPU in one
fresh process (``calibrate.readings``, which skips the look for a chip),
the rest of a run driven as on the chip."""
import json
import os
import subprocess
import sys

import pytest

from bench import spec

DATA = spec.BENCH_DIR / "tests" / "data"
GROUPS = {
    "single": ("tiny.single", 1, [("frozen_state", 1), ("half_batch", 1)]),
    "mesh4": ("tiny.mesh4", 4, [("frozen_state", 1), ("half_batch", 1),
                                ("no_exchange", 1)]),
}


def _calibrate(group):
    cell, devices, faults = GROUPS[group]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(spec.ROOT / "src"),
                                           str(spec.ROOT)]))
    flags = env.get("XLA_FLAGS", "")
    env["XLA_FLAGS"] = \
        f"{flags} --xla_force_host_platform_device_count={devices}".strip()
    code = (
        "import json\n"
        "from bench import calibrate\n"
        f"for r in calibrate.readings({cell!r}, 1, 1, {faults!r}, "
        f"root=__import__('pathlib').Path({str(DATA)!r})):\n"
        "    print(json.dumps(r), flush=True)\n")
    cmd = [sys.executable, "-c", code]
    proc = subprocess.run(cmd, env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return {r["kind"]: r for r in map(json.loads,
                                      proc.stdout.strip().splitlines())}


@pytest.fixture(scope="module")
def single():
    return _calibrate("single")


@pytest.fixture(scope="module")
def mesh4():
    return _calibrate("mesh4")


@pytest.mark.parametrize("kind", ["program", "control", "frozen_state",
                                  "half_batch"])
def test_single_chip_path(single, kind):
    assert single[kind]["correct"] is (kind == "program"), single[kind]


@pytest.mark.parametrize("kind", ["program", "control", "frozen_state",
                                  "half_batch", "no_exchange"])
def test_mesh_path(mesh4, kind):
    assert mesh4[kind]["correct"] is (kind == "program"), mesh4[kind]
