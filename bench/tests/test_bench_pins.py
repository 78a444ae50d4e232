"""Pins of what the dense architecture makes, recorded with the
benchmark's code as it stood before the architecture modules
(``arch/``): the same seed must give the same weight words and
embeddings, the same reference logits (compared exactly, on the CPU),
and the same cost counts. A difference here is a difference in what
the benchmark measures and checks."""

import hashlib
import json
import os
import sys

import jax
import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import harness  # noqa: E402

DENSE = harness.load_arch("dense_gqa")
SEED = 3400000015

# bench_tiny.py's sizes of phi3-medium, and a GELU variant at
# starcoder2's head grouping
TINY = {"swiglu": dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=1,
                       head_dim=16, d_ff=128, vocab=512,
                       activation="swiglu", rope_base=10000.0)}
TINY["gelu"] = dict(TINY["swiglu"], n_heads=6, d_ff=96, activation="gelu",
                    rope_base=1e5)

# sha256 (first 16 hex digits) of each leaf's dtype, shape and bytes
PARAMS = {
    "swiglu": {
        "['embed_tokens']": "1ea8a5ecffb38ae1",
        "['final_norm']['scale']": "800ed95cb408e02d",
        "['groups'][0]['b0']['attn']['wk']": "263cc36c6109ced2",
        "['groups'][0]['b0']['attn']['wo']": "b2cef579787505ea",
        "['groups'][0]['b0']['attn']['wq']": "ed376e1fe67f20ea",
        "['groups'][0]['b0']['attn']['wv']": "fd1064c6f802921d",
        "['groups'][0]['b0']['ln1']['scale']": "eae42367fcc43b92",
        "['groups'][0]['b0']['ln2']['scale']": "eae42367fcc43b92",
        "['groups'][0]['b0']['mlp']['w1']": "8aa6f4185ebfea7d",
        "['groups'][0]['b0']['mlp']['w2']": "ef6120505d47d8d5",
        "['groups'][0]['b0']['mlp']['wg']": "b8447f510ed3d2ac",
        "['unembed']": "9880ae3c5ec053d9",
    },
    "gelu": {
        "['embed_tokens']": "1ea8a5ecffb38ae1",
        "['final_norm']['scale']": "800ed95cb408e02d",
        "['groups'][0]['b0']['attn']['wk']": "93f1f1d1219275b5",
        "['groups'][0]['b0']['attn']['wo']": "cfb7be5dace02ede",
        "['groups'][0]['b0']['attn']['wq']": "3a7fdda912e4e1f8",
        "['groups'][0]['b0']['attn']['wv']": "61d78a6aab483d5b",
        "['groups'][0]['b0']['ln1']['scale']": "eae42367fcc43b92",
        "['groups'][0]['b0']['ln2']['scale']": "eae42367fcc43b92",
        "['groups'][0]['b0']['mlp']['w1']": "fde76cc056253d0a",
        "['groups'][0]['b0']['mlp']['w2']": "181ba9672b63a417",
        "['unembed']": "9880ae3c5ec053d9",
    },
}

# the same digest of each sequence's logits at its rows
LOGITS = {
    ("swiglu", False): ["e6532682924d00d5", "5a3614d812036fad"],
    ("swiglu", True): ["51f8642eb2bd0452", "f397b9d8fb16c0dc"],
    ("gelu", False): ["370ad24ac3c7e7c2", "ca449900654559ce"],
    ("gelu", True): ["06704328d5ea67d6", "22063561b6a08e47"],
}

CONTEXTS = [1, 100, 4095]
# bigcode/starcoder2-15b at published widths, 20 of 40 layers
SC2 = dict(n_layers=20, d_model=6144, n_heads=48, n_kv_heads=4, head_dim=128,
           d_ff=24576, vocab=49152, activation="gelu", rope_base=1e5)
COSTS = {
    "phi3": {"projection_dims": [(5120, 5120), (5120, 1280), (5120, 1280),
                                 (5120, 5120), (5120, 17920), (5120, 17920),
                                 (17920, 5120)],
             "model_flops": 43598151680.0,
             "paged_attention": (1718681600.0, 217292800.0)},
    "sc2": {"projection_dims": [(6144, 6144), (6144, 512), (6144, 512),
                                (6144, 6144), (6144, 24576), (24576, 6144)],
            "model_flops": 49927815168.0,
            "paged_attention": (2062417920.0, 88883200.0)},
}


def _digest(a) -> str:
    a = np.ascontiguousarray(np.asarray(a))
    h = hashlib.sha256(f"{a.dtype}{a.shape}".encode())
    h.update(a.tobytes())
    return h.hexdigest()[:16]


def _cost_model(name):
    if name == "sc2":
        return SC2
    with open(os.path.join(BENCH, "configs", "phi3-medium-14b-pp2.json")) as f:
        return DENSE.model(json.load(f))


@pytest.mark.parametrize("activation", ["swiglu", "gelu"])
def test_weight_words_and_embeddings_pinned(activation):
    params = DENSE.build_params(SEED, TINY[activation])
    got = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(
            params, is_leaf=lambda x: hasattr(x, "words")):
        got[jax.tree_util.keystr(path)] = _digest(getattr(leaf, "words",
                                                          leaf))
    assert got == PARAMS[activation]


@pytest.mark.parametrize("control", [False, True])
@pytest.mark.parametrize("activation", ["swiglu", "gelu"])
def test_reference_logits_pinned(activation, control):
    """Two sequences, the longer across a query block (``BUCKET``)."""
    cfg = TINY[activation]
    rng = np.random.default_rng(5)
    seqs = [list(rng.integers(0, cfg["vocab"], 40)),
            list(rng.integers(0, cfg["vocab"], 530))]
    rows = [np.arange(30, 40), np.arange(500, 530)]
    logits = DENSE.forward_logits(SEED, cfg, seqs, rows, control=control)
    assert [_digest(x) for x in logits] == LOGITS[(activation, control)]


@pytest.mark.parametrize("what", ["projection_dims", "model_flops",
                                  "paged_attention"])
@pytest.mark.parametrize("name", ["phi3", "sc2"])
def test_cost_counts_pinned(name, what):
    model = _cost_model(name)
    want = COSTS[name][what]
    if what == "projection_dims":
        assert DENSE.projection_dims(model) == [(model["n_layers"], want)]
    else:
        assert getattr(DENSE, what)(model, CONTEXTS) == want
