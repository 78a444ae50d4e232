"""The plain reference: a dense GQA decoder in float32 ``jax.numpy``.

It follows the published layer equations of the configurations under
``configs/`` (pre-norm RMSNorm blocks, rotary position embedding on the
first and second halves of each head, grouped-query causal softmax
attention, a SwiGLU or tanh-GELU MLP, a final RMSNorm and an untied
unembedding), with every matmul at ``Precision.HIGHEST`` and no cache,
batching, kernel or wire format. It imports nothing of the program: the
weights are regenerated from the seed by ``weights.py`` and the takum8
words decoded through :func:`takum8_table`, built here from the format's
definition.

It runs layer by layer over a few whole sequences (prompt plus served
tokens), so one layer's float32 weights are all that it holds beside the
activations, and returns the logits at the served positions only.

The control computes the same forward with each decoded weight matrix
rounded to int4 (symmetric, one scale per output column): the next
precision below the configuration's 8-bit weights.
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


@functools.partial(jax.jit, static_argnames=("cfg_items",))
def block(x, n_valid, w, cfg_items):
    """One decoder layer over one sequence ``x [T, d]`` whose first
    ``n_valid`` rows are real (the rest are padding, causally after)."""
    cfg = dict(cfg_items)
    t = x.shape[0]
    h, hkv, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    g = h // hkv
    pos = jnp.arange(t)
    a = rmsnorm(x)
    q = rope(mm(a, w["wq"]).reshape(t, h, hd), pos, cfg["rope_base"])
    k = rope(mm(a, w["wk"]).reshape(t, hkv, hd), pos, cfg["rope_base"])
    v = mm(a, w["wv"]).reshape(t, hkv, hd)
    k = jnp.repeat(k, g, axis=1)          # query head i reads kv head i // g
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
    o = jax.lax.map(lambda args: attend(*args),
                    (qs, jnp.arange(nb) * BUCKET)).reshape(t, h * hd)
    x = x + mm(o, w["wo"])
    a = rmsnorm(x)
    if cfg["activation"] == "swiglu":
        hid = jax.nn.silu(mm(a, w["wg"])) * mm(a, w["w1"])
    else:
        hid = jax.nn.gelu(mm(a, w["w1"]), approximate=True)
    x = x + mm(hid, w["w2"])
    return jnp.where((pos < n_valid)[:, None], x, 0.0)


@functools.partial(jax.jit, static_argnames=("vocab",))
def head(x, rows, unembed, vocab):
    """Logits ``[len(rows), vocab]`` at the given rows."""
    return mm(rmsnorm(x[rows]), unembed[:, :vocab])


@functools.partial(jax.jit, static_argnames=("cfg_items", "control"))
def _layer_weights(layer, key, cfg_items, control):
    words = W.layer_words(key, dict(cfg_items), layer)
    out = {k: decode(v) for k, v in words.items()}
    if control:
        out = {k: int4_columns(v) for k, v in out.items()}
    return out


def forward_logits(seed: int, cfg: dict, seqs: Sequence[List[int]],
                   rows: Sequence[np.ndarray], control: bool = False):
    """Reference logits of each sequence at its ``rows`` (float32 numpy
    arrays ``[len(rows_i), vocab]``). ``control=True``: int4 weights."""
    items = tuple(sorted((k, v) for k, v in cfg.items()
                         if isinstance(v, (int, float, str))))
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
    key = W.base_key(seed)
    for layer in range(cfg["n_layers"]):
        w = _layer_weights(layer, key, items, control)
        xs = [block(x, n, w, items) for x, n in zip(xs, lens)]
        del w
    out = []
    for x, r in zip(xs, rows):
        n = len(r)
        padded = np.full(-(-n // ROWS) * ROWS, r[-1], np.int32)
        padded[:n] = r            # one program per row bucket, not per n
        logits = head(x, jnp.asarray(padded), unemb, cfg["vocab"])
        out.append(np.asarray(logits)[:n])
    return out


def served_gaps(seed: int, cfg: dict, requests, control: bool = False):
    """For each ``(prompt, generated)`` pair: the gap ``max(ref) -
    ref[token]`` of the reference's logits at every served position, for
    the token the program served there. With ``control=True`` also the
    gaps of the control's picks (its own first choice at each position,
    read in the float32 reference), else ``None``: ``(gaps, control)``."""
    seqs = [list(p) + list(g[:-1]) for p, g in requests]
    rows = [np.arange(len(p) - 1, len(p) - 1 + len(g)) for p, g in requests]
    ref = forward_logits(seed, cfg, seqs, rows)

    def gaps(picks):
        return [r.max(axis=-1) - np.take_along_axis(r, t[:, None], -1)[:, 0]
                for r, t in zip(ref, picks)]

    served = gaps([np.asarray(g) for _, g in requests])
    if not control:
        return served, None
    ctl = forward_logits(seed, cfg, seqs, rows, control=True)
    return served, gaps([np.argmax(c, axis=-1) for c in ctl])
