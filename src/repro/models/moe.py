"""Dropless top-k mixture of experts over the experts this device holds.

The router scores every token against all ``n_experts`` in float32 and
picks ``top_k`` of them. This device holds the experts ``[experts_first,
experts_first + experts_held)`` (all of them when ``experts_held`` is 0,
as in training) and computes their part of the result; under expert
parallelism the other chips' experts give the rest, which the exchange
between them would add and which is left out here. Shared experts run
for every token.

Dispatch has no capacity and drops nothing: the selected (token, held
expert) pairs become rows, sorted by expert, each expert's group padded
to whole ``EXPERT_BM``-row tiles, and the SwiGLU's three projections run
as grouped matmuls over that layout: ``ops.expert_matmul`` for wire
stacks on the TPU (``ops.ExpertWire``: the Pallas kernel decodes each
expert's weight tiles in VMEM), ``lax.ragged_dot`` for float stacks and
for decoded wire stacks elsewhere. Each token's result is gathered back
from its own rows and summed in the order of its selections, so it
depends on that token alone, whatever else shares the step.

Scoring (``ModelConfig.router_scoring``):

* ``"softmax"``: gates are the softmax of the top-k logits (granite,
  kimi), with a Switch-style load-balance loss for training;
* ``"sigmoid"`` (DeepSeek-V3's ``DeepseekV3TopkRouter``, K-EXAONE):
  ``s = sigmoid(logits)``; the top-k of ``s + router_bias`` are chosen,
  the bias serving the choice only; gates are the chosen ``s`` over
  their sum, times ``routed_scaling``. The normalisation counts every
  chosen expert, held here or not. No auxiliary loss.

Each call also counts the routed rows and the held experts that got any
(``stats``): the serving counters ``PageStats.moe_rows`` /
``moe_calls`` (``serve/scheduler.py``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.kernels.ops import EXPERT_BM
from repro.models import layers as L

__all__ = ["moe_init", "moe_apply", "route", "rows_bound", "TOKEN_CHUNK"]

# tokens per grouped matmul: bounds the rows of one call, and with them
# the kernel's resident output stripe
TOKEN_CHUNK = 512


def moe_init(key, d_model, d_expert, n_experts, *, held: int = 0,
             n_shared: int = 0, bias: bool = False, dtype=jnp.float32):
    """Router over all ``n_experts``; ``held`` (0 -> all) expert stacks
    ``experts_{wg,w1,w2}``; a shared SwiGLU of width ``n_shared *
    d_expert``; with ``bias`` the sigmoid router's selection bias."""
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    held = held or n_experts
    scale_in = (1.0 / d_model) ** 0.5
    scale_out = (1.0 / d_expert) ** 0.5
    p = {
        "router": L.dense_init(k1, d_model, n_experts, dtype=jnp.float32),
        "experts_wg": jax.random.normal(k2, (held, d_model, d_expert),
                                        dtype) * scale_in,
        "experts_w1": jax.random.normal(k3, (held, d_model, d_expert),
                                        dtype) * scale_in,
        "experts_w2": jax.random.normal(k4, (held, d_expert, d_model),
                                        dtype) * scale_out,
    }
    if bias:
        p["router_bias"] = jnp.zeros((n_experts,), jnp.float32)
    if n_shared:
        p["shared"] = L.mlp_init(k5, d_model, n_shared * d_expert, "swiglu",
                                 dtype)
    return p


def route(params, x, cfg):
    """``x [N, D]`` -> ``(idx [N, k], gates [N, k] f32, aux)``: the
    chosen experts (global ids) and their gates, in float32 over all
    ``cfg.n_experts``."""
    logits = jnp.dot(x.astype(jnp.float32), params["router"],
                     precision=jax.lax.Precision.HIGHEST)
    k = cfg.top_k
    if cfg.router_scoring == "sigmoid":
        s = jax.nn.sigmoid(logits)
        _, idx = jax.lax.top_k(s + params["router_bias"], k)
        g = jnp.take_along_axis(s, idx, axis=-1)
        g = g / (jnp.sum(g, axis=-1, keepdims=True) + 1e-20)
        aux = jnp.zeros((), jnp.float32)
    elif cfg.router_scoring == "softmax":
        vals, idx = jax.lax.top_k(logits, k)
        g = jax.nn.softmax(vals, axis=-1)
        # load-balance aux (Switch): E * mean_e fraction_e * prob_e
        probs = jax.nn.softmax(logits, axis=-1)
        top1 = jax.nn.one_hot(idx[:, 0], cfg.n_experts, dtype=jnp.float32)
        aux = cfg.n_experts * jnp.sum(jnp.mean(top1, axis=0)
                                      * jnp.mean(probs, axis=0))
    else:
        raise ValueError(f"unknown router_scoring {cfg.router_scoring!r}")
    return idx, g * cfg.routed_scaling, aux


def _layout(idx, first: int, held: int, bm: int):
    """Rows of the held selections, grouped by expert in ``bm``-row tiles.

    Returns ``(dest [N*k], group [held], tile_expert, n_used, counts)``:
    the row of each (token, selection) pair in the grouped layout (past
    the end when the expert is not held here), each expert's padded
    group size, the expert of each row tile (unused tiles repeat the
    last used one) and the count of used tiles, and the rows each held
    expert got."""
    n, k = idx.shape
    local = (idx - first).reshape(-1)
    e = jnp.where((local >= 0) & (local < held), local, held)
    counts = jnp.bincount(e, length=held + 1)[:held]
    group = -(-counts // bm) * bm
    ends = jnp.cumsum(group)
    order = jnp.argsort(e, stable=True)
    se = jnp.minimum(e[order], held - 1)
    rank = jnp.arange(n * k) - (jnp.cumsum(counts) - counts)[se]
    m = rows_bound(n, k, held, bm)
    dest_sorted = jnp.where(e[order] < held, ends[se] - group[se] + rank, m)
    dest = jnp.zeros((n * k,), jnp.int32).at[order].set(
        dest_sorted.astype(jnp.int32))
    n_used = (ends[-1] // bm).astype(jnp.int32)
    tiles = jnp.searchsorted(ends, jnp.arange(m // bm) * bm, side="right",
                             method="compare_all")
    last = tiles[jnp.maximum(n_used - 1, 0)]
    tile_expert = jnp.minimum(
        jnp.where(jnp.arange(m // bm) < n_used, tiles, last), held - 1)
    return (dest, group.astype(jnp.int32), tile_expert.astype(jnp.int32),
            n_used.reshape(1), counts)


def rows_bound(n: int, k: int, held: int, bm: int) -> int:
    """Rows of the grouped layout for ``n`` tokens: every selection held
    here, and each expert's last tile partly filled."""
    return -(-(n * min(k, held) + held * (bm - 1)) // bm) * bm


def _grouped(xs, w, group, tile_expert, n_used):
    """``xs [M, K]`` grouped rows times their expert's ``w`` -> ``[M, N]``
    in ``xs``'s dtype. A wire stack runs the Pallas kernel on the TPU;
    elsewhere its words are decoded and the rows multiplied in float32,
    as the kernel does."""
    dtype = xs.dtype
    if isinstance(w, ops.ExpertWire):
        if not ops.interpret_default():
            return ops.expert_matmul(xs, w.words, tile_expert, n_used,
                                     w.spec, bm=EXPERT_BM).astype(dtype)
        xs, w = xs.astype(jnp.float32), w.decode(jnp.float32)
    out = jax.lax.ragged_dot(xs, w.astype(xs.dtype), group,
                             precision=jax.lax.Precision.HIGHEST)
    return out.astype(dtype)


def _routed(params, x, idx, g, first: int, held: int):
    """The held experts' part for ``x [n, D]``: ``(y [n, D], rows,
    calls)``."""
    n, d = x.shape
    k = idx.shape[1]
    with jax.named_scope("moe.route"):
        dest, group, tile_expert, n_used, counts = _layout(idx, first, held,
                                                           EXPERT_BM)
        m = rows_bound(n, k, held, EXPERT_BM)
        xs = jnp.zeros((m, d), x.dtype).at[dest].set(
            jnp.repeat(x, k, axis=0), mode="drop")
    with jax.named_scope("moe.experts"):
        h = jax.nn.silu(_grouped(xs, params["experts_wg"], group,
                                 tile_expert, n_used)) \
            * _grouped(xs, params["experts_w1"], group, tile_expert, n_used)
        out = _grouped(h, params["experts_w2"], group, tile_expert, n_used)
        rows = out[jnp.minimum(dest, m - 1)].reshape(n, k, d)
        wt = jnp.where((dest < m).reshape(n, k), g, 0.0)
        y = jnp.sum(wt[..., None] * rows.astype(jnp.float32), axis=1)
    return (y.astype(x.dtype), jnp.sum(counts).astype(jnp.int32),
            jnp.sum(counts > 0).astype(jnp.int32))


def moe_apply(params, x, cfg):
    """x [B, T, D] -> (out [B, T, D], aux_loss, stats): the shared
    experts plus the gated outputs of the held experts each token chose;
    ``stats`` = ``{"moe_rows", "moe_calls"}`` int32 (routed rows, and
    held experts with rows, summed over the token chunks)."""
    b, t, d = x.shape
    x2 = x.reshape(b * t, d)
    first, held = cfg.experts_first, cfg.experts_held or cfg.n_experts
    with jax.named_scope("moe.route"):
        idx, g, aux = route(params, x2, cfg)
    n = b * t
    if n <= TOKEN_CHUNK:
        y, rows, calls = _routed(params, x2, idx, g, first, held)
    else:
        pad = -n % TOKEN_CHUNK
        chunks = [jnp.pad(a, ((0, pad), (0, 0))).reshape(
            -1, TOKEN_CHUNK, a.shape[1]) for a in (x2, idx, g)]
        # padding tokens hold no rows: their selections point past the
        # held range
        valid = (jnp.arange(n + pad) < n).reshape(-1, TOKEN_CHUNK, 1)
        chunks[1] = jnp.where(valid, chunks[1], first + held)
        y, rows, calls = jax.lax.map(
            lambda c: _routed(params, c[0], c[1], c[2], first, held),
            tuple(chunks))
        y = y.reshape(-1, d)[:n]
        rows, calls = jnp.sum(rows), jnp.sum(calls)
    if "shared" in params:
        with jax.named_scope("moe.shared"):
            y = y + L.mlp(params["shared"], x2, "swiglu")
    return (y.reshape(b, t, d), aux.astype(jnp.float32),
            {"moe_rows": rows, "moe_calls": calls})
