"""BENCHMARK.json and the files it names: every cell, configuration,
traffic mix and metric is found by name, and a cell is added with files
alone."""
import json
import re
import shutil
from pathlib import Path

import numpy as np
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


def _copy_bench(tmp: Path) -> dict:
    """The benchmark's files copied under ``tmp``; returns their bytes."""
    shutil.copytree(ROOT / "bench", tmp / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return {f: f.read_bytes() for f in (tmp / "bench").rglob("*")
            if f.is_file()}


def _unedited(before: dict):
    """Every file that was there before is as it was."""
    assert all(f.read_bytes() == b for f, b in before.items())


def test_a_cell_added_from_files_alone(tmp_path):
    before = _copy_bench(tmp_path)
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    mix = json.loads((ROOT / "bench/traffic/long-decode.json").read_text())
    mix.update(clients=12, prompt_tokens=[64, 512], output_tokens=[32, 256])
    (tmp_path / "bench/traffic/files-alone.json").write_text(
        json.dumps(mix))
    (tmp_path / "bench/metrics/rows_seen.short.py").write_text(
        "def read(run):\n    return 1.0\n")
    spec["workloads"].append({
        "name": "phi35.files-alone", "config": "phi-3.5-mini",
        "traffic": "files-alone", "chips": 1, "why": "control"})
    spec["per_layer"].append({
        "name": "rows_seen.short", "unit": "rows", "better": "higher",
        "source": "program_counter", "layer": "scheduler",
        "moves": "output_tok_s", "workloads": ["phi35.files-alone"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    new = harness.load_spec(tmp_path)
    assert "phi35.files-alone" in harness.cell_names(new)
    c = harness.load_cell(new, "phi35.files-alone", tmp_path)
    assert c.traffic["clients"] == 12
    assert [m["name"] for m in c.per_layer] == ["rows_seen.short"]
    assert harness.reader("rows_seen.short", tmp_path)(None) == 1.0
    old = harness.load_cell(new, "phi35.long-decode", tmp_path)
    assert "rows_seen.short" not in [m["name"] for m in old.per_layer]
    _unedited(before)


#: a configuration shaped like DeepSeek-V2-Lite at a small size: latent
#: attention in every layer, a dense MLP first and sparse experts after
PROGRAM = {
    "layer_pattern": [["mla", "dense"], ["mla", "moe"], ["mla", "moe"]],
    "moe": {"num_experts": 8, "top_k": 2, "expert_d_ff": 32,
            "num_shared_experts": 1, "shared_d_ff": 32},
    "mla": {"kv_lora_rank": 32, "q_lora_rank": 0, "qk_nope_head_dim": 16,
            "qk_rope_head_dim": 8, "v_head_dim": 16}}
#: its reference: the plain sizes, and counts of its own (a token
#: multiplies 1000 weights; attention is left at 1 flop and 1 byte)
STUB_REFERENCE = """
from types import SimpleNamespace


def sizes_of(conf):
    return SimpleNamespace(layers=3, d=64, heads=4, kv_heads=4, head_dim=16,
                           d_ff=128, vocab=512, rope_theta=1e4, eps=1e-6)


def attention_flops(s, rows):
    return 1


def attention_bytes(s, rows):
    return 1


def model_flops(s, rows):
    return 2000 * sum(n for _, n in rows)
"""


def test_a_configuration_added_from_files_alone(tmp_path):
    from repro.configs.base import LayerSpec, MLAConfig, MoEConfig
    from repro.core.paged_runner import paged_supported
    from bench.serve import Record
    from bench.trace import Trace
    from bench.traffic import Request
    before = _copy_bench(tmp_path)
    b = tmp_path / "bench"
    conf = json.loads((b / "configs/phi-3.5-mini.json").read_text())
    conf.update(name="tiny-mla-moe", reference="tiny_mla_moe",
                program=PROGRAM)
    (b / "configs/tiny-mla-moe.json").write_text(json.dumps(conf))
    (b / "references/tiny_mla_moe.py").write_text(STUB_REFERENCE)
    shutil.copy(b / "traffic/long-decode.json", b / "traffic/tiny.json")
    (b / "metrics/mfu.tiny.py").write_text(
        "from bench.readers import mfu\n\n\n"
        "def read(run):\n    return mfu(run, ('_ragged_sample_step',))\n")
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"].append({
        "name": "tiny-mla-moe", "source": conf["source"],
        "file": "bench/configs/tiny-mla-moe.json", "reduced": [],
        "why": "latent attention, sparse experts"})
    spec["workloads"].append({
        "name": "tiny.decode", "config": "tiny-mla-moe", "traffic": "tiny",
        "chips": 1, "why": "latent attention and experts in the fused step"})
    spec["per_layer"].append({
        "name": "mfu.tiny", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "whole step",
        "moves": "output_tok_s", "workloads": ["tiny.decode"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    c = harness.load_cell(harness.load_spec(tmp_path), "tiny.decode",
                          tmp_path)
    assert [m["name"] for m in c.per_layer] == ["mfu.tiny"]
    cfg = harness.program_config(c.conf, tmp_path)
    assert cfg.layer_pattern == (LayerSpec("mla", "dense"),
                                 LayerSpec("mla", "moe"),
                                 LayerSpec("mla", "moe"))
    assert cfg.moe == MoEConfig(**PROGRAM["moe"])
    assert cfg.mla == MLAConfig(**PROGRAM["mla"])
    assert (cfg.n_layers, cfg.d_model, cfg.head_dim) == (3, 64, 16)
    ref = harness.reference(c.conf, tmp_path)
    assert Path(ref.__file__) == b / "references/tiny_mla_moe.py"
    # the whole step's share counts the reference's flops: two rows of
    # 3 tokens over one 4 ms step run at 1e12 flop/s
    req = Request(client=0, index=0, prompt=np.zeros(8, np.int32),
                  max_tokens=6, temperature=0.0, top_p=1.0, seed=0)
    rec = Record(req, t_submit=-1.0, chunks=[(t, 1) for t in
                                             (-0.5, 1, 2, 3, 4, 5, 6)])
    trace = Trace([], [["jit__ragged_sample_step(1)", 0, 4e6, ""]], [],
                  (0.0, 1e7))
    run = harness.Run(cell=c, sizes=ref.sizes_of(c.conf), records=[rec],
                      t_open=0.0, t_close=6.5, stats_open={},
                      stats_close={}, setup_s=1.0,
                      peak={"bf16_flops_per_s": 1e12}, trace=trace, ref=ref)
    assert harness.reader("mfu.tiny", tmp_path)(run) == pytest.approx(
        100 * ref.model_flops(run.sizes, run.window_rows()) / (4e-3 * 1e12))
    assert ref.model_flops(run.sizes, run.window_rows()) == 2000 * 6
    # the paged path serves no latent attention or experts yet
    assert not paged_supported(cfg)
    _unedited(before)


def test_phi_program_is_its_nine_sizes():
    from repro.configs.base import ModelConfig
    conf = json.loads((ROOT / "bench/configs/phi-3.5-mini.json").read_text())
    assert "program" not in conf
    assert harness.program_config(conf) == ModelConfig(
        name="phi-3.5-mini", n_layers=16, d_model=3072, n_heads=32,
        n_kv_heads=32, head_dim=96, d_ff=8192, vocab_size=32064,
        rope_theta=10000.0, norm_eps=1e-5, act="silu", tie_embeddings=False,
        max_context=131072, source=conf["source"])


@pytest.mark.parametrize("program,named", [
    ({"n_expert": 8}, "n_expert"),
    ({"moe": {"num_experts": 8, "top_k": 2, "expert_d_ff": 32,
              "n_group": 1}}, "n_group"),
    ({"layer_pattern": [["mla", "dense"]] * 15 + [["lstm", "dense"]],
      "mla": {}}, "lstm"),
    ({"layer_pattern": [["attn", "dense"]] * 3}, "layer_pattern"),
    ({"n_layers": 3, "d_ff": 128}, "d_ff, n_layers"),
    ({"name": "other", "vocab_size": 512}, "name, vocab_size"),
])
def test_an_unknown_program_key_is_named(program, named):
    conf = json.loads((ROOT / "bench/configs/phi-3.5-mini.json").read_text())
    with pytest.raises(ValueError, match=named):
        harness.program_config(dict(conf, program=program))
