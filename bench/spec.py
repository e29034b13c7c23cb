"""Find the benchmark's data files by name.

A cell (``workloads/<cell>.json``) names its model configuration
(``configs/<config>.json``) and its traffic mix (``traffic/<traffic>.json``);
a per-layer metric is the module ``metrics/<metric>.py``.  Nothing here
knows a cell, a configuration or a metric by name.
"""
from __future__ import annotations

import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


def _load(kind: str, name: str, root: Path = BENCH_DIR) -> dict:
    path = root / kind / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind[:-1]} named {name!r} ({path})")
    with path.open() as f:
        return json.load(f)


def load_cell(name: str, root: Path = BENCH_DIR) -> dict:
    """The cell with its configuration and traffic mix resolved:
    ``cell["config"]`` and ``cell["traffic"]`` become the loaded dicts
    (each with its own ``name``)."""
    cell = _load("workloads", name, root)
    cell["name"] = name
    cfg = _load("configs", cell["config"], root)
    cfg["name"] = cell["config"]
    traffic = _load("traffic", cell["traffic"], root)
    traffic["name"] = cell["traffic"]
    cell["config"], cell["traffic"] = cfg, traffic
    return cell


def benchmark_json(root: Path = ROOT) -> dict:
    with (root / "BENCHMARK.json").open() as f:
        return json.load(f)


def peaks(device_kind: str, root: Path = BENCH_DIR) -> dict:
    """Peak rates of one chip of ``device_kind``; an unknown kind is an
    error, never a default."""
    with (root / "peaks.json").open() as f:
        table = json.load(f)
    try:
        return table["kinds"][device_kind]
    except KeyError:
        raise ValueError(f"no peak rates for device kind {device_kind!r}; "
                         f"known: {sorted(table['kinds'])}") from None
