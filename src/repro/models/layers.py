"""Shared transformer building blocks (functional, explicit param pytrees).

All layers are pure functions ``apply(params, x, ...)`` with matching
``init(key, ...)``; blocks are stackable along a leading layer dim for
``lax.scan`` (compile-time friendly for 96-layer configs on the 512-way
dry-run).

Weight-quantised execution: when a ``QuantConfig.weights`` format is
active (serving), dense projections route through the takum
decode-matmul (kernels/ops.quant_matmul) — the paper's codec as the input
stage of the matmul unit.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.dist import tp as _tp
from repro.dist.sharding import annotate

DTYPES = {"f32": jnp.float32, "bf16": jnp.bfloat16}


def dense_init(key, d_in, d_out, scale: Optional[float] = None,
               dtype=jnp.float32):
    scale = (1.0 / d_in) ** 0.5 if scale is None else scale
    return jax.random.normal(key, (d_in, d_out), dtype) * scale


def rmsnorm_init(d):
    return {"scale": jnp.ones((d,), jnp.float32)}


def rmsnorm(params, x, eps=1e-6):
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * params["scale"]
    return out.astype(dt)


def rope(x, positions, base: float = 10_000.0):
    """x: [..., T, H, hd]; positions: [..., T]."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = jnp.exp(-jnp.log(base) * jnp.arange(half, dtype=jnp.float32)
                    / half)
    ang = positions[..., :, None].astype(jnp.float32) * freqs  # [..., T, half]
    ang = ang[..., None, :]                                    # [..., T, 1, half]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                          axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (GQA, optional sliding window, optional cross-attention, cache)
# ---------------------------------------------------------------------------


def attn_init(key, d_model, n_heads, n_kv_heads, head_dim,
              dtype=jnp.float32, qk_norm: bool = False):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {
        "wq": dense_init(k1, d_model, n_heads * head_dim, dtype=dtype),
        "wk": dense_init(k2, d_model, n_kv_heads * head_dim, dtype=dtype),
        "wv": dense_init(k3, d_model, n_kv_heads * head_dim, dtype=dtype),
        "wo": dense_init(k4, n_heads * head_dim, d_model,
                         scale=(1.0 / (n_heads * head_dim)) ** 0.5,
                         dtype=dtype),
    }
    if qk_norm:
        p["q_norm"] = rmsnorm_init(head_dim)
        p["k_norm"] = rmsnorm_init(head_dim)
    return p


def _proj_qkv(params, x, xa, n_heads, n_kv_heads, head_dim, rope_base,
              positions, use_rope=True, eps=1e-6):
    b = x.shape[0]
    q = (x @ params["wq"]).reshape(b, -1, n_heads, head_dim)
    src = x if xa is None else xa
    k = (src @ params["wk"]).reshape(b, -1, n_kv_heads, head_dim)
    v = (src @ params["wv"]).reshape(b, -1, n_kv_heads, head_dim)
    if "q_norm" in params:   # QK-norm: RMSNorm over each head, pre-RoPE
        q = rmsnorm(params["q_norm"], q, eps)
        k = rmsnorm(params["k_norm"], k, eps)
    if use_rope and xa is None:
        q = rope(q, positions, rope_base)
        k = rope(k, positions, rope_base)
    q = annotate(q, "batch", "seq", "heads", None)
    k = annotate(k, "batch", "seq", "kv_heads", None)
    v = annotate(v, "batch", "seq", "kv_heads", None)
    return q, k, v


def _sdpa(q, k, v, mask):
    """q [B,Tq,H,hd], k/v [B,Tk,Hkv,hd]; GQA via head grouping; f32 softmax."""
    b, tq, h, hd = q.shape
    hkv = k.shape[2]
    g = h // hkv
    q = q.reshape(b, tq, hkv, g, hd)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", q, k).astype(jnp.float32)
    scores = scores / (hd ** 0.5)
    if mask is not None:
        scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v)
    return out.reshape(b, tq, h, hd)


# chunked (memory-efficient / flash-style) attention ------------------------

ATTN_CHUNK_T = 2048   # switch to the chunked path at/above this seq length
QC, KC = 2048, 1024   # query/key chunk sizes (large QC: fewer KV re-reads)

# beyond-paper perf knob (EXPERIMENTS.md §Perf): skip fully-masked KV
# blocks in the causal band. Baseline = off.
import os as _os
CAUSAL_SKIP = _os.environ.get("REPRO_CAUSAL_SKIP", "0") == "1"


def _sdpa_chunked(q, k, v, *, window: int = 0, causal_skip: bool = False,
                  causal: bool = True):
    """Online-softmax attention: never materialises [Tq, Tk] scores.

    Memory per step is [B, Hkv, G, QC, KC]; the outer loop over query
    blocks is a python loop (static), the inner loop over KV blocks a
    ``lax.scan``. With ``causal_skip`` the inner loop only visits KV
    blocks that intersect the causal/window band — the beyond-paper
    useful-FLOPs optimisation recorded in EXPERIMENTS.md §Perf.
    """
    b, tq, h, hd = q.shape
    tk = k.shape[1]
    hkv = k.shape[2]
    g = h // hkv
    assert tq % QC == 0 and tk % KC == 0, (tq, tk)
    q5 = q.reshape(b, tq, hkv, g, hd)
    scale = hd ** -0.5
    nkb = tk // KC
    k_blocks = k.reshape(b, nkb, KC, hkv, hd).swapaxes(0, 1)
    v_blocks = v.reshape(b, nkb, KC, hkv, hd).swapaxes(0, 1)
    kidx = (jnp.arange(nkb) * KC)

    outs = []
    for qb in range(tq // QC):
        q_blk = q5[:, qb * QC:(qb + 1) * QC]            # [B,QC,hkv,g,hd]
        qpos = qb * QC + jnp.arange(QC)
        lo, hi = 0, nkb
        if causal_skip and causal:
            hi = min(nkb, qb + QC // KC + 1)             # blocks above diag
            if window:
                lo = max(0, (qb * QC - window) // KC)

        def kv_step(carry, inp, qpos=qpos, q_blk=q_blk):
            m, l, acc = carry
            kc_, vc_, k0 = inp
            s = jnp.einsum("bqkgd,bskd->bkgqs", q_blk, kc_)
            s = s.astype(jnp.float32) * scale
            kpos = k0 + jnp.arange(KC)
            if causal:
                msk = kpos[None, :] <= qpos[:, None]
                if window:
                    msk = msk & (kpos[None, :] > qpos[:, None] - window)
                s = jnp.where(msk[None, None, None], s, -1e30)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = l * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bkgqs,bskd->bkgqd", p.astype(vc_.dtype), vc_)
            acc = acc * corr[..., None].astype(acc.dtype) + pv
            return (m_new, l, acc), None

        init = (jnp.full((b, hkv, g, QC), -jnp.inf, jnp.float32),
                jnp.zeros((b, hkv, g, QC), jnp.float32),
                jnp.zeros((b, hkv, g, QC, hd), v.dtype))
        (m, l, acc), _ = jax.lax.scan(
            kv_step, init,
            (k_blocks[lo:hi], v_blocks[lo:hi], kidx[lo:hi]))
        out = acc / jnp.maximum(l, 1e-30)[..., None].astype(acc.dtype)
        outs.append(out.transpose(0, 3, 1, 2, 4).reshape(b, QC, h, hd))
    return jnp.concatenate(outs, axis=1)


def causal_mask(tq, tk, offset=0, window=0):
    """[1,1,1,tq,tk] True = attend. offset: query position of row 0."""
    qi = jnp.arange(tq)[:, None] + offset
    kj = jnp.arange(tk)[None, :]
    m = kj <= qi
    if window:
        m = m & (kj > qi - window)
    return m[None, None, None, :, :]


def _encode_kv(k, v, cache, kv_quant: str):
    """Encode fresh k/v ``[B, T, Hkv, hd]`` to the cache's head-major
    wire format: (spec, kw, vw) with kw/vw ``[B, Hkv, T, hd]``.

    One registry lookup; the identity codec casts to the cache dtype,
    wire codecs encode through their ``FormatSpec.encode_tile``."""
    from repro import formats
    spec = formats.resolve(kv_quant)
    k, v = k.swapaxes(1, 2), v.swapaxes(1, 2)
    if spec.is_identity:
        return spec, k.astype(cache["k"].dtype), v.astype(cache["v"].dtype)
    return spec, spec.encode_tile(k.astype(jnp.float32)), \
        spec.encode_tile(v.astype(jnp.float32))


# fused decode-attention dispatch (kernels/takum_attention.py): 'auto'
# follows the backend (Pallas kernel on TPU, jnp decode-then-attend
# fallback elsewhere); '1'/'0' force it for tests and experiments
KV_ATTN_KERNEL = {"1": True, "0": False}.get(
    _os.environ.get("REPRO_KV_ATTN_KERNEL", "auto"))


def attention(params, x, cfg, positions, *, xa=None, mask=None,
              cache: Optional[Dict[str, Any]] = None, window: int = 0,
              bidirectional: bool = False, prefill_fresh: bool = False,
              rope: bool = True):
    """Self- or cross-attention with optional decode cache.

    cache (self-attn decode): {"k","v": [B, Hkv, Tmax, hd], "pos": scalar},
    or the paged form {"k","v": [P, Hkv, ps, hd], "table": [B, NP],
    "pos"/"start": [B]} built by ``transformer.init_paged_cache``.
    ``rope=False``: no position embedding (a NoPE layer). ``q_norm`` /
    ``k_norm`` in ``params`` (``cfg.qk_norm``) RMS-normalise each head of
    q and k after projection. Returns (out, new_cache).
    """
    h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q, k, v = _proj_qkv(params, x, xa, h, hkv, hd, cfg.rope_base, positions,
                        use_rope=rope and xa is None, eps=cfg.norm_eps)
    new_cache = None
    if (cache is not None and xa is None and prefill_fresh
            and "start" not in cache
            and x.shape[1] >= ATTN_CHUNK_T and x.shape[1] % QC == 0):
        # fresh prefill (pos == 0): fill the cache, but compute attention
        # with the chunked kernel over the *current* k/v — the cache-read
        # path would materialise [Tq, Tk] scores (tens of GB at 32k)
        pos = cache["pos"]
        _, kw, vw = _encode_kv(k, v, cache, cfg.kv_quant)
        ck = jax.lax.dynamic_update_slice(cache["k"], kw, (0, 0, pos, 0))
        cv = jax.lax.dynamic_update_slice(cache["v"], vw, (0, 0, pos, 0))
        new_cache = {"k": ck, "v": cv, "pos": pos + x.shape[1]}
        out = _sdpa_chunked(q, k, v, window=window, causal_skip=CAUSAL_SKIP,
                            causal=True)
    elif cache is not None and xa is None and "table" in cache:
        # paged decode (serve.paged): the cache is a [P, Hkv, ps, hd]
        # page pool shared by the batch, "table" [B, NP] maps each
        # sequence's KV block to a pool page, and "pos"/"start" are
        # per-sequence vectors (continuous batching packs unequal
        # lengths). Append this step's wire word at
        # (table[b, pos // ps], pos % ps), then attend through
        # ops.paged_attention — pages are gathered by the block table
        # inside the fused kernel (or its gather-then-attend oracle).
        if x.shape[1] != 1:
            raise ValueError(
                "paged KV caches are decode-only (one token per step); "
                "prefill runs on a contiguous cache and is scattered "
                "into pages by the scheduler")
        pos = cache["pos"]                                       # (B,)
        spec, kw, vw = _encode_kv(k, v, cache, cfg.kv_quant)
        ps = cache["k"].shape[2]
        # clamp the block index to the table width: idle scheduler
        # slots keep stepping with a stale pos and must stay in-table
        # (they point at the reserved scratch page)
        pidx = jnp.minimum(pos // ps, cache["table"].shape[1] - 1)
        page = jnp.take_along_axis(cache["table"], pidx[:, None], 1)[:, 0]
        off = pos % ps
        ck = cache["k"].at[page, :, off].set(kw[:, :, 0])
        cv = cache["v"].at[page, :, off].set(vw[:, :, 0])
        new_cache = dict(cache, k=ck, v=cv, pos=pos + 1)
        from repro.kernels import ops as kops
        out = kops.paged_attention(
            q, ck, cv, cache["table"], spec, pos=pos,
            start=cache["start"], window=window,
            use_kernel=KV_ATTN_KERNEL).astype(x.dtype)
    elif cache is not None and xa is None:
        # decode / cached-prefill: append this step's k/v in wire format,
        # then attend straight over the wire-format cache through
        # ops.takum_attention — words are decoded tile-by-tile inside the
        # fused flash kernel (or by its decode-then-attend jnp oracle
        # off-TPU), so the full-precision [B, Hkv, Tmax, hd] K/V never
        # exist in HBM. The uncompressed cache rides the same op with
        # fmt="none" (identity encoding).
        pos = cache["pos"]
        spec, kw, vw = _encode_kv(k, v, cache, cfg.kv_quant)
        ck = jax.lax.dynamic_update_slice(cache["k"], kw, (0, 0, pos, 0))
        cv = jax.lax.dynamic_update_slice(cache["v"], vw, (0, 0, pos, 0))
        new_cache = {"k": ck, "v": cv, "pos": pos + x.shape[1]}
        start = cache.get("start")
        if start is not None:
            new_cache["start"] = start
        from repro.kernels import ops as kops
        out = kops.takum_attention(
            q, ck, cv, spec.n, spec, pos=pos, start=start, window=window,
            use_kernel=KV_ATTN_KERNEL,
            block=cfg.kv_block or None).astype(x.dtype)
    elif (cache is None and xa is None and x.shape[1] >= ATTN_CHUNK_T
            and x.shape[1] % QC == 0 and k.shape[1] % KC == 0):
        out = _sdpa_chunked(q, k, v, window=window,
                            causal_skip=CAUSAL_SKIP,
                            causal=not bidirectional)
    else:
        out = _sdpa(q, k, v, mask)
    out = out.reshape(x.shape[0], x.shape[1], h * hd)
    # TP seam (dist.tp): identity ``out @ wo`` outside a sharded step;
    # inside one, the all-gather/all-reduce collective (error-feedback
    # residuals ride new_cache when serve/shard.py injected them)
    out, new_cache = _tp.attn_out(out, params["wo"], new_cache)
    return annotate(out, "batch", "seq", "embed"), new_cache


# ---------------------------------------------------------------------------
# MLP variants
# ---------------------------------------------------------------------------


def mlp_init(key, d_model, d_ff, activation, dtype=jnp.float32):
    if activation == "swiglu":
        k1, k2, k3 = jax.random.split(key, 3)
        return {"wg": dense_init(k1, d_model, d_ff, dtype=dtype),
                "w1": dense_init(k2, d_model, d_ff, dtype=dtype),
                "w2": dense_init(k3, d_ff, d_model, dtype=dtype)}
    k1, k2 = jax.random.split(key)
    return {"w1": dense_init(k1, d_model, d_ff, dtype=dtype),
            "w2": dense_init(k2, d_ff, d_model, dtype=dtype)}


def mlp(params, x, activation):
    out, _ = mlp_tp(params, x, activation)
    return out


def mlp_tp(params, x, activation, state=None):
    """``mlp`` with the TP seam exposed: ``state`` is the layer's
    attention-cache dict, threaded through ``dist.tp.mlp_out`` so the
    down-projection's error-feedback residual (``tp_res_m``) can ride
    the scan carry next to the KV pages. Identity pass-through when no
    TP context is active."""
    if activation == "swiglu":
        hid = jax.nn.silu(x @ params["wg"]) * (x @ params["w1"])
    elif activation == "relu2":
        hid = jax.nn.relu(x @ params["w1"]) ** 2
    elif activation == "gelu":
        hid = jax.nn.gelu(x @ params["w1"])
    else:
        raise ValueError(activation)
    hid = annotate(hid, "batch", "seq", "ff")
    out, state = _tp.mlp_out(hid, params["w2"], state)
    return annotate(out, "batch", "seq", "embed"), state


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------


VOCAB_PAD = 128  # embedding tables padded so the vocab dim shards cleanly


def padded_vocab(vocab: int) -> int:
    return -(-vocab // VOCAB_PAD) * VOCAB_PAD


def embed_init(key, vocab, d_model, tie: bool, dtype=jnp.float32):
    k1, k2 = jax.random.split(key)
    vp = padded_vocab(vocab)
    p = {"embed_tokens": jax.random.normal(k1, (vp, d_model), dtype) * 0.02}
    if not tie:
        p["unembed"] = dense_init(k2, d_model, vp, dtype=dtype)
    return p


def embed(params, tokens, dtype):
    out = params["embed_tokens"][tokens].astype(dtype)
    return annotate(out, "batch", "seq", "embed")


def unembed(params, x, vocab: Optional[int] = None):
    if "unembed" in params:
        w = params["unembed"]
    else:
        w = params["embed_tokens"].T.astype(x.dtype)
    # DP seam (dist.tp): plain ``x @ w`` outside a sharded step; inside
    # one, batch rows shard over the data axis and logits all-gather
    logits = _tp.unembed_rows(x, w)
    logits = annotate(logits.astype(jnp.float32), "batch", "seq", "vocab")
    if vocab is not None and logits.shape[-1] != vocab:
        logits = logits[..., :vocab]  # drop the vocab padding
    return logits


def xent_loss(logits, labels, mask=None):
    """Mean token cross-entropy in f32; labels [B, T] int32."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is None:
        return jnp.mean(nll)
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
