"""Compile-only checks of the main-path Pallas kernels for a TPU v5e.

Each test lowers a kernel through Mosaic for one chip of a *described*
``v5e:2x2`` topology (no chip attached) at the widths the chip smoke
serves — phi3-medium-14b: d_model 5120, d_ff 17920, 40 heads over 10 KV
heads of 128, 256-position pages, 32 decode rows — and asserts the
compiled program holds a ``tpu_custom_call``. Blocks are the ones the
TPU would use: the ``|tpu`` entries of ``kernels/autotune_defaults.json``,
else the blockless defaults (``quant_matmul``'s shape-derived tile).
Interpret-mode tests cannot see what this file guards: tiling rules,
unsupported primitives and VMEM limits.

The topology is described inside a module fixture, never at import: the
TPU library admits one process at a time, and every test worker imports
this file.
"""

import json

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import formats
from repro.kernels import autotune, ops

D, FF, H, HKV, HD = 5120, 17920, 40, 10, 128
PAGE, DECODE_BATCH, CONTEXT = 256, 32, 1088


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means: cannot
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(one_chip, fn, *shapes):
    """HLO text of ``fn`` compiled for the described chip."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    txt = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in txt
    return txt


def _tpu_blocks(op, fmt, bucket, fallback):
    return autotune.lookup(op, fmt, bucket, backend="tpu") or fallback


@pytest.mark.parametrize("name", ["takum8", "takum16", "posit8"])
def test_codec_decode_lowers(one_chip, name):
    spec = formats.get(name)
    _compile(one_chip, lambda w: ops.takum_decode(w, spec, interpret=False),
             ((D, FF), spec.word_dtype))


@pytest.mark.parametrize("name", ["takum8", "takum16", "posit8"])
def test_codec_encode_lowers(one_chip, name):
    spec = formats.get(name)
    _compile(one_chip, lambda x: ops.takum_encode(x, spec, interpret=False),
             ((D, FF), jnp.float32))


def test_posit8_table_decode_lowers(one_chip, monkeypatch):
    monkeypatch.setenv("REPRO_LUT_DECODE", "1")
    spec = formats.get("posit8")
    assert spec.lut_decode
    # a block of its own: the table is chosen at trace time, and the
    # default block may already be traced with the computed decode
    _compile(one_chip, lambda w: ops.takum_decode(w, spec, interpret=False,
                                                  block=(128, 128)),
             ((D, FF), jnp.uint8))


@pytest.mark.parametrize("k,n", [(D, D), (D, HKV * HD), (D, FF), (FF, D)],
                         ids=["wq_wo", "wk_wv", "wg_w1", "w2"])
@pytest.mark.parametrize("m", [DECODE_BATCH, PAGE],
                         ids=["decode", "prefill_chunk"])
def test_quant_matmul_lowers(one_chip, m, k, n):
    blocks = _tpu_blocks("qmatmul", "takum8",
                         autotune.matmul_bucket(m, k, n),
                         ops.qmm_blocks(m, k, n))
    _compile(one_chip,
             lambda x, w: ops.quant_matmul(x, w, "takum8", True, False,
                                           blocks),
             ((m, k), jnp.bfloat16), ((k, n), jnp.uint8))


def test_lns_matmul_lowers(one_chip):
    blocks = _tpu_blocks("lns_qmatmul", "lns-takum8",
                         autotune.matmul_bucket(DECODE_BATCH, D, FF),
                         ops.default_qmm_blocks(DECODE_BATCH))
    _compile(one_chip,
             lambda x, w: ops.lns_matmul(x, w, "lns-takum8", "linear", True,
                                         False, blocks),
             ((DECODE_BATCH, D), jnp.float32), ((D, FF), jnp.uint8))


@pytest.mark.parametrize("tq", [1, PAGE], ids=["decode", "prefill_chunk"])
def test_takum_attention_lowers(one_chip, tq):
    (bk,) = ops.resolved_blocks("attention", "takum8", CONTEXT)
    bk = _tpu_blocks("attention", "takum8",
                     autotune.attention_bucket(CONTEXT), (bk,))[0]
    kv = ((1, HKV, CONTEXT, HD), jnp.uint8)
    _compile(one_chip,
             lambda q, k, v: ops.takum_attention(
                 q, k, v, 0, "takum8", pos=CONTEXT - 64, use_kernel=True,
                 interpret=False, block=bk),
             ((1, tq, H, HD), jnp.bfloat16), kv, kv)


def test_paged_attention_lowers(one_chip):
    npg = CONTEXT // PAGE
    pool = ((DECODE_BATCH * npg + 1, HKV, PAGE, HD), jnp.uint8)
    _compile(one_chip,
             lambda q, k, v, t, p: ops.paged_attention(
                 q, k, v, t, "takum8", pos=p, use_kernel=True,
                 interpret=False),
             ((DECODE_BATCH, 1, H, HD), jnp.bfloat16), pool, pool,
             ((DECODE_BATCH, npg), jnp.int32), ((DECODE_BATCH,), jnp.int32))


def _seeded_tpu_entries():
    with open(autotune.DEFAULTS_PATH) as f:
        entries = json.load(f)["entries"]
    return sorted(k for k in entries if k.endswith("|tpu"))


@pytest.mark.parametrize("key", _seeded_tpu_entries())
def test_seeded_tpu_table_blocks_lower(one_chip, key):
    """Every hand-seeded ``|tpu`` autotune entry compiles at a shape of
    its own bucket."""
    op, fmt, bucket, _ = key.split("|")
    blocks = autotune.lookup(op, fmt, bucket, backend="tpu")
    spec = formats.get(fmt)
    if op == "attention":
        t = int(bucket[1:])
        kv = ((1, HKV, t, HD),
              jnp.bfloat16 if spec.is_identity else spec.word_dtype)
        _compile(one_chip,
                 lambda q, k, v: ops.takum_attention(
                     q, k, v, 0, spec, pos=t - 1, use_kernel=True,
                     interpret=False, block=blocks[0]),
                 ((1, 1, H, HD), jnp.bfloat16), kv, kv)
        return
    m, rest = bucket[1:].split("k")
    k, n = rest.split("n")
    fn = ops.quant_matmul if op == "qmatmul" else (
        lambda x, w, s, u, i, b: ops.lns_matmul(x, w, s, "linear", u, i, b))
    _compile(one_chip, lambda x, w: fn(x, w, spec, True, False, blocks),
             ((int(m), int(k)), jnp.bfloat16),
             ((int(k), int(n)), spec.word_dtype))


# K-EXAONE-236B-A23B's expert layer: d_model 6144, experts of 2048, 16
# experts held, top-8 of 128 routed; the grouped rows of a 128-row decode
# step and of a 256-row prefill chunk
EXP_D, EXP_FF, EXP_HELD, EXP_TOPK = 6144, 2048, 16, 8


@pytest.mark.parametrize("tokens", [128, 256])
@pytest.mark.parametrize("k,n", [(EXP_D, EXP_FF), (EXP_FF, EXP_D)])
def test_expert_matmul_lowers(one_chip, tokens, k, n):
    from repro.models.moe import rows_bound
    m = rows_bound(tokens, EXP_TOPK, EXP_HELD, ops.EXPERT_BM)
    spec = formats.get("takum8")
    txt = _compile(
        one_chip,
        lambda x, w, te, nu: ops.expert_matmul(
            x, w, te, nu, spec, interpret=False),
        ((m, k), jnp.bfloat16), ((EXP_HELD, k, n), jnp.uint8),
        ((m // ops.EXPERT_BM,), jnp.int32),
        ((1,), jnp.int32))
    assert "expert_matmul" in txt
