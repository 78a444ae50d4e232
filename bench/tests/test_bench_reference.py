"""The plain reference: its takum8 decode and the dense architecture's
forward pass, against the program at a tiny size on the CPU (float32
compute, no KV format), for both activation families."""

import os
import sys

import jax
import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import harness  # noqa: E402
import reference  # noqa: E402
import takum8  # noqa: E402
import weights  # noqa: E402

DENSE = harness.load_arch("dense_gqa")


def _table():
    """``takum8.value`` of every word, as float32."""
    with np.errstate(over="ignore"):
        return np.array([takum8.value(w) for w in range(256)],
                        np.float64).astype(np.float32)


def test_takum8_table_matches_the_codec():
    import jax.numpy as jnp
    from repro.core import takum
    words = jnp.arange(256, dtype=jnp.int32).astype(jnp.uint8)
    prog = np.asarray(takum.takum_to_float(words, 8, dtype=jnp.float32))
    mine = _table()
    both_nan = np.isnan(prog) & np.isnan(mine)
    assert np.array_equal(prog[~both_nan], mine[~both_nan])
    assert both_nan.sum() == 1


def test_arithmetic_decode_matches_the_table():
    import jax.numpy as jnp
    got = np.asarray(reference.decode(jnp.arange(256).astype(jnp.uint8)))
    want = _table()
    want[np.abs(want) < 2.0 ** -126] = 0.0      # below float32's normals
    assert np.array_equal(got, want, equal_nan=True)


def test_weight_words_round_a_gaussian():
    w = weights.gaussian_words(jax.random.PRNGKey(0), (256, 256),
                               256 ** -0.5)
    v = np.asarray(reference.decode(w))
    assert abs(v.std() - 256 ** -0.5) < 0.05 * 256 ** -0.5
    assert abs(v.mean()) < 0.01


TINY = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
            d_ff=96, vocab=300, rope_base=10000.0)


@pytest.mark.parametrize("activation,heads", [("swiglu", (4, 2)),
                                              ("gelu", (6, 1))])
def test_reference_matches_the_program_forward(activation, heads):
    from repro.configs.base import ModelConfig
    from repro.models import transformer
    cfg = dict(TINY, activation=activation, n_heads=heads[0],
               n_kv_heads=heads[1], rope_base=1e5 if activation == "gelu"
               else 1e4)
    mc = ModelConfig(name="tiny", family="dense", dtype="f32",
                     **{k: v for k, v in cfg.items()})
    params = DENSE.build_params(3, cfg)
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg["vocab"], 40)
    with jax.default_matmul_precision("highest"):
        prog, _ = transformer.forward(params, jax.numpy.asarray(toks[None]),
                                        mc)
    rows = np.arange(5, 40)
    ref = DENSE.forward_logits(3, cfg, [list(toks)], [rows])[0]
    got = np.asarray(prog[0, rows])
    scale = np.abs(ref).max()
    assert np.abs(got - ref).max() <= 1e-4 * scale
