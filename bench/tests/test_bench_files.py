"""Every file BENCHMARK.json names loads by name, and agrees with it."""
import importlib.util
import json

import pytest

from bench import spec

BENCH = spec.benchmark_json()
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]


@pytest.mark.parametrize("name", CELLS)
def test_cell_loads_with_its_config_and_traffic(name):
    entry = next(w for w in BENCH["workloads"] if w["name"] == name)
    cell = spec.load_cell(name)
    assert cell["config"]["name"] == entry["config"]
    assert cell["traffic"]["name"] == entry["traffic"]
    assert cell["chips"] == entry["chips"]
    assert cell["limits"], "a cell's check needs limits"


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file_matches_its_entry(entry):
    with open(spec.ROOT / entry["file"]) as f:
        cfg = json.load(f)
    assert cfg["source"] == entry["source"]
    assert cfg["reduced"] == entry["reduced"]
    importlib.import_module(f"bench.configs.{cfg['reference']}")


@pytest.mark.parametrize("name", METRICS)
def test_metric_reader_loads(name):
    path = spec.BENCH_DIR / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(f"m_{name}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    assert callable(mod.read)


def test_peaks_known_kind_and_unknown_kind_raises():
    v5e = spec.peaks("TPU v5 lite")
    assert v5e["peak_flops_bf16"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["ici_bytes_per_s_per_link"] == 50e9
    with pytest.raises(ValueError, match="no peak rates"):
        spec.peaks("TPU v9 imaginary")
