"""Seeded random weights of a dense GQA decoder, made on the device.

Projection weights are takum8 wire words, drawn per layer as a Gaussian
of standard deviation ``d_in ** -0.5`` rounded to the nearest takum8
word, sampled from 32-bit random draws by integer compares
(:func:`gaussian_words`). Embedding and
unembedding are float32, as the serving path keeps them. Norm scales are
ones. Everything derives from ``--seed`` through ``jax.random``, so the
harness and the reference make the same words from the same seed: the
reference regenerates them here and decodes them with its own
arithmetic (``reference.decode``), never reading what the program holds.

All layers come out of one jitted call that writes one layer slice at a
time into the stacked word arrays (``lax.fori_loop``), so no float32
stack of all layers ever exists on the device.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

import takum8

VOCAB_PAD = 128
EMBED_STD = 0.02


def padded_vocab(vocab: int) -> int:
    return -(-vocab // VOCAB_PAD) * VOCAB_PAD


def projection_shapes(cfg: dict) -> dict:
    """``{leaf: (group, d_in, d_out)}`` of one layer's projections."""
    d, h, hkv, hd, ff = (cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"],
                         cfg["head_dim"], cfg["d_ff"])
    shapes = {"wq": ("attn", d, h * hd), "wk": ("attn", d, hkv * hd),
              "wv": ("attn", d, hkv * hd), "wo": ("attn", h * hd, d)}
    if cfg["activation"] == "swiglu":
        shapes["wg"] = ("mlp", d, ff)
    shapes["w1"] = ("mlp", d, ff)
    shapes["w2"] = ("mlp", ff, d)
    return shapes


@functools.lru_cache(maxsize=None)
def magnitude_thresholds(std: float):
    """``((threshold, count), ...)``: where a uniform 31-bit draw ``u``
    steps from one takum8 magnitude word to the next. Positive words are
    in value order, so ``|N(0, std^2)|`` rounded to the nearest word is
    word ``j`` = the sum of the counts of the thresholds ``<= u``; a
    threshold is the probability, scaled to 2^31, that ``|x|`` lies
    below the midpoint of two neighbouring words. Thresholds at 0 are
    always passed, and words whose lower midpoint the scale cannot
    reach (far in the tail) are never drawn, so no NaR or overflowing
    word comes out."""
    vals = [takum8.value(w) for w in range(128)]
    mids = [(a + b) / 2 for a, b in zip(vals[:-1], vals[1:])]
    top = 2 ** 31 - 1
    out = {}
    for m in mids:
        t = int(math.erf(m / (std * math.sqrt(2))) * 2 ** 31)
        if t >= top:
            break
        out[t] = out.get(t, 0) + 1
    return tuple(sorted(out.items()))


def gaussian_words(key, shape, std: float):
    """Takum8 words of ``N(0, std^2)`` rounded to the nearest word, made
    with elementwise integer compares only (no gather, which runs one
    element at a time on a TPU): 31 bits of a 32-bit draw pick the
    magnitude, the last one the sign (negation is the two's complement
    of the word)."""
    u = jax.random.bits(key, shape, jnp.uint32)
    mag = (u >> 1).astype(jnp.int32)
    j = jnp.zeros(shape, jnp.int32)
    for t, count in magnitude_thresholds(float(std)):
        j = j + (count if t == 0 else jnp.where(mag >= t, count, 0))
    j = jnp.where((u & 1) == 1, (256 - j) & 0xFF, j)
    return j.astype(jnp.uint8)


def base_key(seed: int):
    """The run's root key: any seed up to 64 bits."""
    return jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31)),
                              seed // (2 ** 31))


def layer_words(key, cfg: dict, layer) -> dict:
    """Takum8 words ``{leaf: [d_in, d_out] uint8}`` of one layer
    (``key`` from :func:`base_key`; ``layer`` may be traced):
    N(0, 1/d_in) rounded to takum8."""
    out = {}
    lkey = jax.random.fold_in(key, 1 + layer)
    for i, (name, (_, din, dout)) in enumerate(
            sorted(projection_shapes(cfg).items())):
        out[name] = gaussian_words(jax.random.fold_in(lkey, i), (din, dout),
                                   din ** -0.5)
    return out


@functools.partial(jax.jit, static_argnums=(1, 2))
def _embeddings(key, vocab: int, d: int):
    ke, ku = jax.random.split(jax.random.fold_in(key, 0))
    vp = padded_vocab(vocab)
    emb = jax.random.normal(ke, (vp, d), jnp.float32) * EMBED_STD
    unemb = jax.random.normal(ku, (d, vp), jnp.float32) * d ** -0.5
    return emb, unemb


def embeddings(seed: int, cfg: dict):
    """(embed [Vp, d], unembed [d, Vp]) float32, from one jitted call so
    the harness and the reference get the same bits."""
    return _embeddings(base_key(seed), cfg["vocab"], cfg["d_model"])


@functools.partial(jax.jit, static_argnums=(1,))
def _build(key, cfg_items):
    cfg = dict(cfg_items)
    n = cfg["n_layers"]
    shapes = projection_shapes(cfg)
    stacks = {name: jnp.zeros((n, din, dout), jnp.uint8)
              for name, (_, din, dout) in shapes.items()}

    def body(layer, stacks):
        words = layer_words(key, cfg, layer)
        return {name: jax.lax.dynamic_update_index_in_dim(
                    stacks[name], words[name], layer, 0)
                for name in stacks}

    return jax.lax.fori_loop(0, n, body, stacks)


def build_params(seed: int, cfg: dict):
    """The served parameter tree: stacked ``WireMatrix`` takum8 words
    for every projection, float32 embedding and unembedding, unit norm
    scales. The tree's layout is the one ``ServeEngine`` consumes."""
    from repro.kernels.ops import WireMatrix
    stacks = _build(base_key(seed), tuple(sorted(cfg.items())))
    emb, unemb = embeddings(seed, cfg)
    n, d = cfg["n_layers"], cfg["d_model"]
    block = {"ln1": {"scale": jnp.ones((n, d), jnp.float32)},
             "ln2": {"scale": jnp.ones((n, d), jnp.float32)},
             "attn": {}, "mlp": {}}
    for name, (group, _, _) in projection_shapes(cfg).items():
        block[group][name] = WireMatrix(stacks[name], fmt="takum8")
    return {"embed_tokens": emb, "unembed": unemb,
            "final_norm": {"scale": jnp.ones((d,), jnp.float32)},
            "groups": [{"b0": block}]}
