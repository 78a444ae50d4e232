"""The plain reference's shared pieces: float32 ``jax.numpy`` at
``Precision.HIGHEST``, with no cache, batching, kernel or wire format.

It imports nothing of the program. An architecture module
(``arch/<arch>.py``) regenerates its weights from the seed through
``weights.py``, decodes the takum8 words with :func:`decode`, built here
from the format's definition, and writes its own layer from the pieces
below: :func:`rmsnorm`, :func:`rope`, :func:`causal_attention`.
:func:`forward_logits` runs such a layer layer by layer over a few whole
sequences (prompt plus served tokens), so one layer's float32 weights
are all that it holds beside the activations, and returns the logits at
the served positions only; :func:`served_gaps` turns them into the
check's gaps.

The control computes the same forward with each decoded weight matrix
rounded to int4 (:func:`int4_columns`: symmetric, one scale per output
column), the next precision below the configurations' 8-bit weights.
"""

from __future__ import annotations

import functools
import math
from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

import weights as W

HI = jax.lax.Precision.HIGHEST
EPS = 1e-6
BUCKET = 512
ROWS = 128


def decode(words):
    """Takum8 words to float32 by ``takum8.value``'s arithmetic,
    elementwise (a table gather runs one element at a time on a TPU).
    Characteristics below float32's normal range decode to zero, above
    it to infinity; NaR decodes to NaN."""
    w = words.astype(jnp.int32)
    neg = w >= 0x80
    m = jnp.where(neg, (256 - w) & 0xFF, w)       # magnitude word
    d = (m >> 6) & 1
    reg = (m >> 3) & 7
    r = jnp.where(d == 1, reg, 7 - reg)
    tail = m & 7
    p = jnp.maximum(3 - r, 0)
    c_bits = jnp.where(r <= 3, tail >> p, tail << jnp.maximum(r - 3, 0))
    frac = jnp.where(r <= 3, tail & ((1 << p) - 1), 0)
    c = jnp.where(d == 1, (1 << r) - 1 + c_bits, 1 - (1 << (r + 1)) + c_bits)
    scale = jax.lax.bitcast_convert_type(
        (jnp.clip(c, -126, 127) + 127) << 23, jnp.float32)
    scale = jnp.where(c < -126, 0.0, jnp.where(c > 127, jnp.inf, scale))
    v = (1.0 + frac.astype(jnp.float32) / (1 << p).astype(jnp.float32)) \
        * scale
    v = jnp.where(m == 0, 0.0, jnp.where(m == 0x80, jnp.nan, v))
    return jnp.where(neg, -v, v)


def int4_columns(w):
    """Symmetric int4 rounding, one scale per output column."""
    s = jnp.max(jnp.abs(w), axis=0, keepdims=True) / 7.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.clip(jnp.round(w / s), -7, 7) * s


def rmsnorm(x):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + EPS)


def rope(x, positions, base):
    """x [T, H, hd]: rotate (first half, second half) pairs."""
    half = x.shape[-1] // 2
    freqs = jnp.exp(-math.log(base) * jnp.arange(half, dtype=jnp.float32)
                    / half)
    ang = positions[:, None, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def causal_attention(q, k, v):
    """Grouped-query causal softmax attention of one sequence: ``q [T, H,
    hd]``, ``k`` and ``v [T, Hkv, hd]`` (query head i reads KV head
    ``i // (H // Hkv)``), in query blocks of ``BUCKET`` rows; ``[T, H *
    hd]``."""
    t, h, hd = q.shape
    g = h // k.shape[1]
    pos = jnp.arange(t)
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)

    def attend(qb, q0):
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI) / math.sqrt(hd)
        qpos = q0 + jnp.arange(qb.shape[0])
        mask = pos[None, :] <= qpos[:, None]
        s = jnp.where(mask[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HI)

    nb = t // BUCKET
    qs = q.reshape(nb, BUCKET, h, hd)
    return jax.lax.map(lambda args: attend(*args),
                       (qs, jnp.arange(nb) * BUCKET)).reshape(t, h * hd)


@functools.partial(jax.jit, static_argnames=("vocab",))
def head(x, rows, unembed, vocab):
    """Logits ``[len(rows), vocab]`` at the given rows."""
    return mm(rmsnorm(x[rows]), unembed[:, :vocab])


def forward_logits(seed: int, cfg: dict, seqs: Sequence[List[int]],
                   rows: Sequence[np.ndarray], layer, control: bool = False):
    """Reference logits of each sequence at its ``rows`` (float32 numpy
    arrays ``[len(rows_i), vocab]``). ``layer(i, xs, lens)`` runs layer
    ``i`` over every sequence ``xs`` (``[T, d]``, padded to a multiple of
    ``BUCKET``; the first ``lens`` rows real) and returns them; the
    embeddings are ``weights.embeddings``'. ``control=True``: int4
    unembedding (the layer rounds its own weights)."""
    emb, unemb = W.embeddings(seed, cfg)
    if control:
        unemb = int4_columns(unemb)
    xs, lens = [], []
    for s in seqs:
        t = -(-len(s) // BUCKET) * BUCKET
        tok = np.zeros(t, np.int32)
        tok[:len(s)] = s
        xs.append(emb[jnp.asarray(tok)])
        lens.append(len(s))
    for i in range(cfg["n_layers"]):
        xs = layer(i, xs, lens)
    out = []
    for x, r in zip(xs, rows):
        n = len(r)
        padded = np.full(-(-n // ROWS) * ROWS, r[-1], np.int32)
        padded[:n] = r            # one program per row bucket, not per n
        logits = head(x, jnp.asarray(padded), unemb, cfg["vocab"])
        out.append(np.asarray(logits)[:n])
    return out


def served_gaps(forward, requests, control: bool = False):
    """For each ``(prompt, generated)`` pair: the gap ``max(ref) -
    ref[token]`` of the reference's logits at every served position, for
    the token the program served there. ``forward(seqs, rows,
    control=False)`` gives the logits (an architecture's
    ``forward_logits`` with its seed and sizes bound). With
    ``control=True`` also the gaps of the control's picks (its own first
    choice at each position, read in the float32 reference), else
    ``None``: ``(gaps, control)``."""
    seqs = [list(p) + list(g[:-1]) for p, g in requests]
    rows = [np.arange(len(p) - 1, len(p) - 1 + len(g)) for p, g in requests]
    ref = forward(seqs, rows)

    def gaps(picks):
        return [r.max(axis=-1) - np.take_along_axis(r, t[:, None], -1)[:, 0]
                for r, t in zip(ref, picks)]

    served = gaps([np.asarray(g) for _, g in requests])
    if not control:
        return served, None
    ctl = forward(seqs, rows, control=True)
    return served, gaps([np.argmax(c, axis=-1) for c in ctl])
