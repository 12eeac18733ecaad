"""Operation and byte counts against values worked by hand."""
import json
from pathlib import Path

import pytest

from bench import flops
from bench.references.gqa_decoder import Sizes, sizes_of

CONFIGS = Path(__file__).resolve().parents[1] / "configs"
FILE = sizes_of(json.loads((CONFIGS / "phi-3.5-mini.json").read_text()))
PHI = FILE._replace(layers=32)              # the published depth
YI = Sizes(layers=32, d=4096, heads=32, kv_heads=4, head_dim=128,
           d_ff=11008, vocab=64000, rope_theta=5e6, eps=1e-5)


def test_phi_sizes_from_its_file():
    # the cell holds the first 16 of the 32 published layers
    assert FILE == Sizes(16, 3072, 32, 32, 96, 8192, 32064, 10000.0, 1e-5)


@pytest.mark.parametrize("s, per_layer, total", [
    # 3072^2 (q) + 2 * 3072^2 (k, v; 32 kv heads of 96) + 3072^2 (o)
    # + 3 * 3072 * 8192 (gate, up, down)
    (PHI, 113_246_208, 3_820_879_872),
    # 4096^2 + 2 * 4096 * 512 + 4096^2 + 3 * 4096 * 11008
    (YI, 173_015_040, 6_060_769_280),
])
def test_params(s, per_layer, total):
    assert flops.layer_params(s) == 32 * per_layer
    # the published sizes: 3.8B and 6.06B with embedding and head
    assert flops.layer_params(s) + 2 * s.d * s.vocab == total


def test_decode_row_phi():
    # one token at position 1023 attends to 1024 positions:
    # 4 * 32 layers * 32 heads * 96 * 1024
    assert flops.attention_flops(PHI, [(1023, 1)]) == 402_653_184
    # K and V of 1024 positions (32 kv heads x 96) + q and o, bf16, 32 layers
    kv = 2 * 1024 * 32 * 96
    qo = 2 * 1 * 32 * 96
    assert flops.attention_bytes(PHI, [(1023, 1)]) == 32 * 2 * (kv + qo)
    assert 32 * 2 * (kv + qo) == 403_046_400


def test_prefill_chunk_yi():
    # 256 tokens from position 0: 1 + 2 + ... + 256 = 32896 pairs
    assert flops.attention_flops(YI, [(0, 256)]) == (
        4 * 32 * 32 * 128 * 32896)
    # context of 256 positions on 4 kv heads, queries of 32 heads
    assert flops.attention_bytes(YI, [(0, 256)]) == 32 * 2 * (
        2 * 256 * 4 * 128 + 2 * 256 * 32 * 128)


def test_model_flops_adds_head_per_row():
    rows = [(100, 1), (0, 16)]
    want = (2 * flops.layer_params(YI) * 17 + 2 * 2 * 4096 * 64000
            + flops.attention_flops(YI, rows))
    assert flops.model_flops(YI, rows) == want
