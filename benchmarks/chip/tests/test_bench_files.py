"""Every cell of BENCHMARK.json resolves, by name, to its files; names,
units and keys keep to the benchmark's contract."""
import json
import re

import pytest

import tiny
import harness

SPEC = harness.benchmark_spec()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in SPEC["workloads"]]


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["benchmarks/chip"]
    assert 1 <= SPEC["run_seconds"] <= 51
    assert any(m["name"] == "setup_s" for m in SPEC["end_to_end"])


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves(name):
    cell = harness.resolve_cell(name)
    harness.load_module("drivers", cell.traffic["driver"])
    harness.load_module("references", cell.config["reference"])
    assert cell.config["name"] == cell.cell["config"]
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in cell.per_layer:
        assert hasattr(harness.load_module("layer_metrics", m["name"]),
                       "read")
        assert m["moves"] in {e["name"] for e in cell.end_to_end}
    for k, v in cell.cell["limits"].items():
        assert NAME.match(k) and v > 0


def test_names_units_and_keys():
    seen = set()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in SPEC[group]:
            assert NAME.match(entry["name"]), entry["name"]
            assert (group, entry["name"]) not in seen
            seen.add((group, entry["name"]))
            if "unit" in entry:
                assert UNIT.match(entry["unit"])
                assert entry["better"] in ("lower", "higher")
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert json.loads(open(harness.ROOT / c["file"]).read())["name"] \
            == c["name"]
    four = [w for w in SPEC["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(SPEC["workloads"]) // 2)
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert len(w["why"]) <= 200
        assert w["why"] == harness.load_json("workloads", w["name"])["why"]


def test_peaks_table():
    peak = harness.load_peaks("TPU v5 lite")
    assert peak["bf16_flops_per_s"] == 197e12
    assert peak["hbm_bytes_per_s"] == 819e9
    with pytest.raises(harness.BenchError):
        harness.load_peaks("TPU v99")


def test_metric_workloads_name_cells():
    cells = {w["name"]: w for w in SPEC["workloads"]}
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        for c in m.get("workloads", []):
            assert c in cells, (m["name"], c)
        # collectives exist only across chips: one chip reads nothing
        if m["name"].startswith("mar_"):
            assert all(cells[c]["chips"] > 1 for c in m["workloads"])
