"""Seeded random weights that every architecture shares, made on the
device.

Wire words are takum8 words of a Gaussian rounded to the nearest word,
sampled from 32-bit random draws by integer compares
(:func:`gaussian_words`). Embedding and unembedding are float32, as the
serving path keeps them (:func:`embeddings`). Everything derives from
``--seed`` through ``jax.random`` (:func:`base_key`), so the harness and
the reference make the same words from the same seed: the reference
regenerates them and decodes them with its own arithmetic
(``reference.decode``), never reading what the program holds. Which
leaves a model has, and the key each is drawn from, is its architecture
module's (``arch/<arch>.py``).
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
