"""The FLOP and byte functions against values worked by hand for
Mistral-7B-v0.3 (hidden 4096, 32 layers, 32/8 heads of 128, MLP 14336,
vocabulary 32768, untied)."""

import json
import os

import pytest

from perfbench import flops
from perfbench.manifest import BENCH_DIR


def _shape(name):
    with open(os.path.join(BENCH_DIR, "configs", name + ".json")) as f:
        return json.load(f)


FULL, HALF = _shape("mistral-7b-v0.3-train4"), _shape("mistral-7b-v0.3-serve1")
# one layer: wq + wo 2 x 4096 x 4096, wk + wv 2 x 4096 x 1024, MLP 3 x 4096 x 14336
LAYER = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336
HEAD = 4096 * 32768


def test_parameter_counts():
    assert LAYER == 218_103_808
    assert flops.layer_matmul_params(FULL) == LAYER
    assert flops.matmul_params(FULL) == 32 * LAYER + HEAD == 7_113_539_584
    # the published count: + the embedding table and 65 norm vectors
    assert flops.total_params(FULL) == 7_248_023_552
    assert flops.total_params(FULL) == FULL["published"]["parameters"]
    assert flops.total_params(HALF) == 16 * (LAYER + 8192) + 4096 + 2 * HEAD


def test_train_flops():
    per_token = 6 * 7_113_539_584 + 6 * 4096 * 4096 * 32
    assert flops.train_flops_per_token(FULL, 4096) == per_token \
        == 45_902_462_976
    assert flops.train_flops_per_step(FULL, 2, 4096) == per_token * 8192
    # 100 % of four v5e chips is 0.477 s a step
    assert flops.mfu_pct(per_token * 8192, 0.4772, 4, 197e12) == \
        pytest.approx(100.0, rel=1e-3)


def test_decode_bytes():
    weights = 16 * LAYER + HEAD + 16 * 2 * 4096 + 4096 + 16 * 4096
    assert flops.decode_min_bytes(HALF, 0, 16) == 2 * weights
    # one live position: K and V rows of 8 heads x 128 in each of 16 layers
    assert flops.decode_min_bytes(HALF, 1, 16) - 2 * weights == \
        2 * (2 * 8 * 128 * 16)
    # weights alone at 819 GB/s: 8.85 ms a step
    need = flops.decode_min_bytes(HALF, 0, 16)
    assert need / 819e9 == pytest.approx(8.85e-3, rel=1e-2)
    assert flops.roofline_pct(need, need / 819e9, 819e9) == \
        pytest.approx(100.0)
