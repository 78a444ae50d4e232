"""Pure-jnp oracles for every Pallas kernel in this package.

Each kernel in ``takum_codec.py`` / ``quantize.py`` / ``takum_matmul.py``
must match its oracle here bit-exactly (codec) or to accumulation
tolerance (matmul) across the shape/dtype sweeps in
``tests/test_kernels.py`` and the registry-parametrised suite in
``tests/test_formats_registry.py``.

These oracles call the *same* ``FormatSpec`` codec hooks as the kernels
(``spec.decode_tile`` / ``spec.encode_tile`` — for linear takum that is
the integer-only ``takum.takum_to_float`` / ``float_to_takum``
reconstruction), so kernel, fallback and reference paths are
bit-identical by construction; the retained ldexp-dataflow reference
lives separately as ``takum.takum_to_float_ref`` and is pinned against
the integer path in ``tests/test_int_reconstruct.py``.

Every entry point resolves its format argument through
``repro.formats.resolve``, so callers may pass a ``FormatSpec``, a
registry name (``"posit8"``), a legacy kind string plus width, or — the
original API — a bare int width meaning linear takum.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import formats

__all__ = ["decode_ref", "encode_ref", "fake_quant_ref", "qmatmul_ref",
           "lns_decode_ref", "fake_quant_lns_ref", "lns_qmatmul_ref",
           "attention_ref", "paged_attention_ref", "expert_matmul_ref"]


def decode_ref(words, fmt, dtype=jnp.float32):
    """wire words -> float (``fmt``: spec / name / int width = linear)."""
    return formats.resolve(fmt).decode_tile(words, dtype=dtype)


def encode_ref(x, fmt):
    """float32 -> wire words (RNE, saturating)."""
    return formats.resolve(fmt).encode_tile(x)


def fake_quant_ref(x, fmt, dtype=jnp.float32):
    """fused quantise-dequantise (no scaling; scaling lives a level up)."""
    spec = formats.resolve(fmt)
    return spec.decode_tile(spec.encode_tile(x), dtype=dtype)


def lns_decode_ref(words, n: int, dtype=jnp.float32):
    """takum-LNS words -> float (tau of Definition 1 on representation
    (10)); legacy alias for ``decode_ref(words, ("lns", n))``."""
    return formats.resolve("lns", n).decode_tile(words, dtype=dtype)


def fake_quant_lns_ref(x, n: int, dtype=jnp.float32):
    """Fused quantise-dequantise on the *logarithmic* takum grid."""
    return fake_quant_ref(x, formats.resolve("lns", n), dtype=dtype)


def qmatmul_ref(x, w_words, fmt, out_dtype=jnp.float32):
    """x [M, K] float  @  decode(w_words [K, N])  -> [M, N] float.

    The weight-only-quantised matmul: weights live in HBM as wire words
    (any float-decoding format — linear takum or the posit baseline)
    and are decoded on the way into the MXU.
    """
    w = formats.resolve(fmt).decode_tile(w_words, dtype=jnp.float32)
    return jnp.dot(x.astype(jnp.float32), w,
                   preferred_element_type=jnp.float32).astype(out_dtype)


def lns_qmatmul_ref(x, w_words, fmt, out_dtype=jnp.float32):
    """XLA fallback for the LNS matmul: activations quantised to the LNS
    grid, both sides decoded to f32, one fused dot.

    Versus the Pallas kernel (which adds the int32 ``ell`` lanes and
    exponentiates the *sum*), each product here carries one extra f32
    multiply rounding — bounded by half an ulp per product, far below the
    n <= 16 quantisation noise. The demo-scale exact-ℓ̄ reference is
    ``core.lns.lns_matmul``.
    """
    spec = formats.resolve_lns(fmt)
    xq = spec.decode_tile(spec.encode_tile(jnp.asarray(x, jnp.float32)))
    w = spec.decode_tile(w_words)
    return jnp.dot(xq, w,
                   preferred_element_type=jnp.float32).astype(out_dtype)


def expert_matmul_ref(x, w_words, tile_expert, n_used, fmt, *, bm: int,
                      bn: int, bk: int):
    """Oracle of ``expert_matmul_kernel_call``: row tile ``t`` of ``x [M,
    K]`` times expert ``tile_expert[t]``'s decoded ``w_words [E, K, N]``,
    zero from tile ``n_used[0]`` on. Each ``(bm, bk) @ (bk, bn)`` part is
    computed and summed over ``kk`` in the kernel's order, so the result
    is bit-identical to the kernel in interpret mode."""
    spec = formats.resolve(fmt)
    m, k = x.shape
    nn = w_words.shape[-1]

    def tile(t):
        xt = jax.lax.dynamic_slice_in_dim(x, t * bm, bm).astype(jnp.float32)
        w = w_words[tile_expert[t]]
        cols = []
        for j in range(nn // bn):
            acc = None
            for kk in range(k // bk):
                wt = spec.decode_tile(w[kk * bk:(kk + 1) * bk,
                                        j * bn:(j + 1) * bn],
                                      dtype=jnp.float32)
                part = jnp.dot(xt[:, kk * bk:(kk + 1) * bk], wt,
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)
                acc = part if acc is None else acc + part
            cols.append(acc)
        out = jnp.concatenate(cols, axis=1)
        return jnp.where(t < n_used[0], out, 0.0)

    return jax.lax.map(tile, jnp.arange(m // bm)).reshape(m, nn)


def attention_ref(q, k_cache, v_cache, n, fmt="none", *, pos,
                  start=None, window: int = 0, out_dtype=jnp.float32):
    """Decode-then-attend oracle for the fused takum attention kernel.

    Exactly the pre-kernel serving path: the **whole** KV cache is
    decoded to f32 up front (the HBM materialisation the Pallas kernel
    exists to avoid) and dense masked attention runs over it. q is
    ``[B, tq, H, hd]``, the head-major caches ``[B, Hkv, Tmax, hd]`` wire words
    (floats for the identity codec); ``pos`` is the position of
    ``q[:, 0]``, ``start`` the per-sequence first valid key position
    (left padding), ``window`` a sliding-window length (0 = full
    causal). All-masked query rows (``qpos < start``) produce finite
    garbage — a uniform average — never NaN; NaR words in *valid*
    positions decode to NaN and poison the rows attending to them.
    """
    spec = formats.resolve(fmt, n)
    if spec.is_identity:
        # stored-dtype K/V (the pre-kernel behaviour): only scores and
        # softmax run in f32, so a bf16 cache costs no extra traffic
        k, v = k_cache, v_cache
    else:
        k = spec.decode_tile(k_cache, dtype=jnp.float32)
        v = spec.decode_tile(v_cache, dtype=jnp.float32)
    b, tq, h, hd = q.shape
    hkv, tk = k.shape[1], k.shape[2]
    g = h // hkv
    q5 = q.reshape(b, tq, hkv, g, hd)
    if not spec.is_identity:
        q5 = q5.astype(jnp.float32)
    scores = (jnp.einsum("bqkgd,bksd->bkgqs", q5, k).astype(jnp.float32)
              * (hd ** -0.5))
    qi = (pos + jnp.arange(tq))[None, None, None, :, None]
    kj = jnp.arange(tk)[None, None, None, None, :]
    m = kj <= qi
    if window:
        m = m & (kj > qi - window)
    if start is not None:
        m = m & (kj >= jnp.asarray(start)[:, None, None, None, None])
    scores = jnp.where(m, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bksd->bqkgd", probs.astype(v.dtype), v)
    return out.reshape(b, tq, h, hd).astype(out_dtype)


def paged_attention_ref(q, k_pool, v_pool, table, fmt="none", *, pos,
                        start=None, window: int = 0,
                        out_dtype=jnp.float32):
    """Gather-then-attend oracle for the paged decode kernel.

    ``k_pool``/``v_pool`` are ``[P, Hkv, ps, hd]`` page pools (wire words
    or floats for the identity codec) and ``table [B, NP]`` holds each
    sequence's page ids. Each sequence's block table gathers its pages
    back into a contiguous ``[Hkv, NP * ps, hd]`` cache, and
    :func:`attention_ref` — exactly the contiguous decode-then-attend
    oracle — runs per sequence (vmapped) with that sequence's own
    ``pos``/``start`` scalar (continuous batching packs unequal-length
    sequences, so both are ``(B,)`` vectors here). Pages past ``pos``
    hold stale words from previous owners; the causal mask excludes
    them, matching the kernel's semantics.
    """
    spec = formats.resolve(fmt)
    b = q.shape[0]
    hkv, hd = k_pool.shape[1], k_pool.shape[3]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    start = (jnp.zeros((b,), jnp.int32) if start is None
             else jnp.broadcast_to(jnp.asarray(start, jnp.int32), (b,)))

    def one(q1, tab, p1, s1):
        kc = k_pool[tab].swapaxes(0, 1).reshape(hkv, -1, hd)
        vc = v_pool[tab].swapaxes(0, 1).reshape(hkv, -1, hd)
        return attention_ref(q1[None], kc[None], vc[None], spec.n, spec,
                             pos=p1, start=s1[None], window=window,
                             out_dtype=out_dtype)[0]

    return jax.vmap(one)(q, jnp.asarray(table, jnp.int32), pos, start)
