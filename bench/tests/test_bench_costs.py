"""FLOP and byte functions (``costs.py`` and the dense architecture's
counts) against hand counts at both configurations' shapes, and the
peaks table."""

import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import costs  # noqa: E402
import harness  # noqa: E402

DENSE = harness.load_arch("dense_gqa")


def _model(name):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return DENSE.model(json.load(f))


def _layer_dims(model):
    """One layer's weight matmuls: every dense layer runs the same."""
    (layers, dims), = DENSE.projection_dims(model)
    assert layers == model["n_layers"]
    return dims


PHI3 = _model("phi3-medium-14b-pp2")
# bigcode/starcoder2-15b at published widths, 20 of 40 layers
SC2 = dict(n_layers=20, d_model=6144, n_heads=48, n_kv_heads=4, head_dim=128,
           d_ff=24576, vocab=49152, activation="gelu", rope_base=1e5)


def test_layer_weights_match_the_published_shapes():
    # phi3-medium: q/o 5120x5120, k/v 5120x1280, gate/up 5120x17920,
    # down 17920x5120; starcoder2-15b: q/o 6144x6144, k/v 6144x512,
    # up 6144x24576, down 24576x6144
    phi3 = sum(k * n for k, n in _layer_dims(PHI3))
    sc2 = sum(k * n for k, n in _layer_dims(SC2))
    assert phi3 == 2 * 5120 * 5120 + 2 * 5120 * 1280 + 3 * 5120 * 17920
    assert phi3 == 340_787_200
    assert sc2 == 2 * 6144 * 6144 + 2 * 6144 * 512 + 2 * 6144 * 24576
    assert sc2 == 383_778_816


def test_qmm_counts():
    f, b = costs.qmm(32, 5120, 17920)
    assert f == 2 * 32 * 5120 * 17920
    assert b == 32 * 5120 * 2 + 5120 * 17920 * 1 + 32 * 17920 * 4


@pytest.mark.parametrize("model,per_layer", [(PHI3, 340_787_200),
                                             (SC2, 383_778_816)])
def test_qmm_over_a_layer(model, per_layer):
    """What ``quant_matmul_roofline`` sums for one layer of one step."""
    rows = 32
    calls = [costs.qmm(rows, k, n) for k, n in _layer_dims(model)]
    acts = sum(rows * k * 2 + rows * n * 4 for k, n in _layer_dims(model))
    assert sum(f for f, _ in calls) == 2 * rows * per_layer
    assert sum(b for _, b in calls) == per_layer + acts


def test_paged_attention_counts():
    f, b = DENSE.paged_attention(PHI3, [1000, 2000])
    assert f == 4 * 40 * 128 * 3000 * 20
    assert b == 2 * 10 * 128 * 3000 * 20 + 2 * 2 * 40 * 128 * 4 * 20
    f, b = DENSE.paged_attention(SC2, [64])
    assert f == 4 * 48 * 128 * 64 * 20
    assert b == 2 * 4 * 128 * 64 * 20 + 2 * 48 * 128 * 4 * 20


def test_model_flops_counts():
    f = DENSE.model_flops(PHI3, [100, 101])
    per_tok = 2 * 340_787_200 * 20 + 2 * 5120 * 32064
    assert f == 2 * per_tok + 4 * 40 * 128 * 20 * (100 + 101)


def test_roofline_time_and_peaks():
    peak = costs.peaks("TPU v5 lite")
    assert peak["bf16_flops_per_s"] == 197e12
    assert peak["hbm_bytes_per_s"] == 819e9
    t, bound = costs.roofline_time(*costs.qmm(32, 5120, 17920), peak)
    assert bound == "memory"
    assert t == pytest.approx((32 * 5120 * 2 + 5120 * 17920
                               + 32 * 17920 * 4) / 819e9)
    t, bound = costs.roofline_time(*costs.qmm(4096, 5120, 17920), peak)
    assert bound == "compute"
    with pytest.raises(KeyError):
        costs.peaks("cpu")
