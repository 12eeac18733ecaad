"""BENCHMARK.json and the files it names: every cell, configuration,
traffic mix and metric is found by name, and a cell is added with files
alone."""
import json
import re
import shutil
from pathlib import Path

import pytest

from bench import harness

ROOT = Path(__file__).resolve().parents[2]
SPEC = harness.load_spec(ROOT)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_names_units_and_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in SPEC[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert any(m["name"] == "setup_s" for m in SPEC["end_to_end"])
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e and "\n" not in m["layer"]


@pytest.mark.parametrize("cell", harness.cell_names(SPEC))
def test_every_cell_loads_with_its_readers(cell):
    c = harness.load_cell(SPEC, cell, ROOT)
    assert any(m["name"] == "setup_s" for m in c.end_to_end)
    assert len(c.end_to_end) >= 2 and c.per_layer
    for m in c.end_to_end + c.per_layer:
        assert callable(harness.reader(m["name"], ROOT))
    harness.reference(c.conf).sizes_of(c.conf)
    assert harness.warm_buckets(c)


@pytest.mark.parametrize("c", SPEC["configs"])
def test_config_files_state_what_they_cut(c):
    conf = json.loads((ROOT / c["file"]).read_text())
    assert conf["source"] == c["source"]
    assert conf["reduced"] == c["reduced"]
    assert set(conf["why_reduced"]) == set(c["reduced"])


def test_a_cell_added_from_files_alone(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    mix = json.loads((ROOT / "bench/traffic/long-decode.json").read_text())
    mix.update(clients=12, prompt_tokens=[64, 512], output_tokens=[32, 256])
    (tmp_path / "bench/traffic/short-unshared.json").write_text(
        json.dumps(mix))
    (tmp_path / "bench/metrics/rows_seen.short.py").write_text(
        "def read(run):\n    return 1.0\n")
    spec["workloads"].append({
        "name": "phi35.short-unshared", "config": "phi-3.5-mini",
        "traffic": "short-unshared", "chips": 1, "why": "control"})
    spec["per_layer"].append({
        "name": "rows_seen.short", "unit": "rows", "better": "higher",
        "source": "program_counter", "layer": "scheduler",
        "moves": "output_tok_s", "workloads": ["phi35.short-unshared"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    new = harness.load_spec(tmp_path)
    assert "phi35.short-unshared" in harness.cell_names(new)
    c = harness.load_cell(new, "phi35.short-unshared", tmp_path)
    assert c.traffic["clients"] == 12
    assert [m["name"] for m in c.per_layer] == ["rows_seen.short"]
    assert harness.reader("rows_seen.short", tmp_path)(None) == 1.0
    old = harness.load_cell(new, "phi35.long-decode", tmp_path)
    assert "rows_seen.short" not in [m["name"] for m in old.per_layer]
