"""The benchmark definition is driven by data: every cell and metric in
``BENCHMARK.json`` resolves to its files by name, a cell added as files
alone is found without editing any file that exists, and the definition
keeps to the limits its format sets."""
import importlib.util
import json
import re
import shutil
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

from harness import spec  # noqa: E402

DEF = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in DEF["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_resolves_to_its_files(cell):
    c = spec.resolve_cell(DEF, cell)
    assert c.config_data["name"] == c.config
    assert c.traffic_data["streams"]
    names = {m.name for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer, f"{cell} reports no per-layer metric"
    for m in c.end_to_end + c.per_layer:
        assert callable(spec.load_reader(m.name))


def test_per_layer_metrics_move_a_metric_their_cells_report():
    e2e = {m["name"]: m for m in DEF["end_to_end"]}
    for m in DEF["per_layer"]:
        assert m["moves"] in e2e
        for cell in m["workloads"]:
            assert cell in CELLS
            scope = e2e[m["moves"]].get("workloads", CELLS)
            assert cell in scope, (m["name"], cell)


def test_definition_keeps_to_its_format():
    assert set(DEF) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert 1 <= DEF["run_seconds"] <= 51
    for c in DEF["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("perfbench/")
        assert all(NAME.match(k) for k in c["reduced"])
        data = json.loads((ROOT / c["file"]).read_text())
        assert data["source"] == c["source"]
        assert sorted(data["reduced"]) == sorted(c["reduced"])
        for k in c["reduced"]:
            assert not k.endswith(("_dim", "_rank", "_size")), k
    fours = [w for w in DEF["workloads"] if w["chips"] == 4]
    assert len(fours) <= max(1, len(DEF["workloads"]) // 2)
    for w in DEF["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
        assert w["chips"] in (1, 4)
    for m in DEF["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in DEF["end_to_end"] + DEF["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    assert any(m["name"] == "setup_s" for m in DEF["end_to_end"])
    for m in DEF["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        if m["unit"] == "%" and ("roofline" in m["name"]
                                 or "mfu" in m["name"]):
            assert m["better"] == "higher"


def test_a_cell_added_as_files_alone_is_found(tmp_path):
    """Copy the benchmark, add a traffic file, a configuration, a metric
    reader and their entries, and resolve the new cell with the copied
    harness: no existing file of the benchmark was edited."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (root / "perfbench").rglob("*")
              if p.is_file()}
    bench = json.loads(json.dumps(DEF))
    (root / "perfbench/traffic/mixed.json").write_text(json.dumps(
        {"lead_s": 5, "streams": [
            {"tier": "standard", "arrival": "poisson", "rate": 1.0,
             "prompt": {"median": 64, "sigma": 0.5, "lo": 8, "hi": 256},
             "output": {"median": 16, "sigma": 0.5, "lo": 4, "hi": 64}}]}))
    cfg = json.loads((BENCH / "configs/qwen3-4b.json").read_text())
    cfg["name"] = "other-model"
    (root / "perfbench/configs/other-model.json").write_text(
        json.dumps(cfg))
    (root / "perfbench/metrics/new_counter.py").write_text(
        "def read(rec):\n    return 42.0\n")
    bench["configs"].append({"name": "other-model",
                             "source": cfg["source"],
                             "file": "perfbench/configs/other-model.json",
                             "reduced": cfg["reduced"], "why": "test"})
    bench["workloads"].append({"name": "other-model.mixed",
                               "config": "other-model", "traffic": "mixed",
                               "chips": 1, "why": "test"})
    if "workloads" in bench["end_to_end"][0]:
        bench["end_to_end"][0]["workloads"].append("other-model.mixed")
    bench["per_layer"].append({"name": "new_counter", "unit": "count",
                               "better": "lower", "source":
                               "program_counter", "layer": "kernels",
                               "moves": bench["end_to_end"][0]["name"],
                               "workloads": ["other-model.mixed"]})
    mod_spec = importlib.util.spec_from_file_location(
        "copied_spec", root / "perfbench/harness/spec.py")
    copied = importlib.util.module_from_spec(mod_spec)
    sys.modules["copied_spec"] = copied
    mod_spec.loader.exec_module(copied)
    c = copied.resolve_cell(bench, "other-model.mixed")
    assert c.config_data["name"] == "other-model"
    assert c.traffic_data["streams"][0]["rate"] == 1.0
    assert [m.name for m in c.per_layer] == ["new_counter"]
    assert copied.load_reader("new_counter")(None) == 42.0
    after = {p: p.read_bytes() for p in before}
    assert after == before


def test_a_configuration_runs_its_departures_over_the_published_values():
    for c in DEF["configs"]:
        data = json.loads((ROOT / c["file"]).read_text())
        run = spec.served(data)
        for k, v in data.get("departures", {}).items():
            assert run[k] == v and k not in c["reduced"]
        cells = [w["name"] for w in DEF["workloads"]
                 if w["config"] == c["name"]]
        for cell in cells:
            assert spec.resolve_cell(DEF, cell).config_data == run


def test_missing_files_are_refused():
    bad = json.loads(json.dumps(DEF))
    bad["workloads"][0]["traffic"] = "no-such-traffic"
    with pytest.raises(spec.SpecError):
        spec.resolve_cell(bad, bad["workloads"][0]["name"])
    with pytest.raises(spec.SpecError):
        spec.resolve_cell(DEF, "no-such-cell")
