"""Encoder-decoder model (seamless-m4t family).

Encoder: bidirectional self-attention transformer over precomputed
modality-frontend embeddings (the audio frontend is a STUB per the
assignment: ``input_specs()`` supplies frame embeddings [B, T_a, D]).
Decoder: causal self-attention + cross-attention to the encoder output,
standard text decoder. Both stacks scan over layers.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L

DTYPES = {"f32": jnp.float32, "bf16": jnp.bfloat16}


def _enc_block_init(key, cfg, dtype):
    k1, k2 = jax.random.split(key)
    return {"ln1": L.rmsnorm_init(cfg.d_model), "ln2": L.rmsnorm_init(cfg.d_model),
            "attn": L.attn_init(k1, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                                cfg.hd, dtype),
            "mlp": L.mlp_init(k2, cfg.d_model, cfg.d_ff, cfg.activation,
                              dtype)}


def _dec_block_init(key, cfg, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    return {"ln1": L.rmsnorm_init(cfg.d_model),
            "ln_x": L.rmsnorm_init(cfg.d_model),
            "ln2": L.rmsnorm_init(cfg.d_model),
            "attn": L.attn_init(k1, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                                cfg.hd, dtype),
            "xattn": L.attn_init(k2, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                                 cfg.hd, dtype),
            "mlp": L.mlp_init(k3, cfg.d_model, cfg.d_ff, cfg.activation,
                              dtype)}


def init(key, cfg: ModelConfig):
    dtype = DTYPES[cfg.param_dtype]
    ks = jax.random.split(key, 4)
    params: Dict[str, Any] = L.embed_init(ks[0], cfg.vocab, cfg.d_model,
                                          cfg.tie_embeddings, dtype)
    params["final_norm"] = L.rmsnorm_init(cfg.d_model)
    params["enc_norm"] = L.rmsnorm_init(cfg.d_model)
    params["enc"] = jax.vmap(lambda k: _enc_block_init(k, cfg, dtype))(
        jax.random.split(ks[1], cfg.n_enc_layers))
    params["dec"] = jax.vmap(lambda k: _dec_block_init(k, cfg, dtype))(
        jax.random.split(ks[2], cfg.n_layers))
    return params


def encode(params, media, cfg: ModelConfig, *, remat: bool = False):
    """media [B, T_a, D] (frontend stub output) -> encoder states."""
    from repro.models.transformer import cast_params
    dtype = DTYPES[cfg.dtype]
    params = cast_params(params, dtype)
    x = media.astype(dtype)
    b, ta, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(ta), (b, ta))

    def block(x, p):
        h, _ = L.attention(p["attn"], L.rmsnorm(p["ln1"], x), cfg, positions,
                           mask=None, bidirectional=True)
        x = x + h
        x = x + L.mlp(p["mlp"], L.rmsnorm(p["ln2"], x), cfg.activation)
        return x

    if remat:
        block = jax.checkpoint(block,
                               policy=jax.checkpoint_policies.nothing_saveable)
    x, _ = jax.lax.scan(lambda c, p: (block(c, p), None), x, params["enc"])
    return L.rmsnorm(params["enc_norm"], x)


def decode(params, tokens, enc_out, cfg: ModelConfig, *,
           caches=None, pos=0, remat: bool = False, features: bool = False):
    """Decoder forward; trains (caches=None) or serves (with KV caches)."""
    from repro.models.transformer import cast_params
    dtype = DTYPES[cfg.dtype]
    params = cast_params(params, dtype)
    b, t = tokens.shape
    x = L.embed(params, tokens, dtype)
    positions = pos + jnp.broadcast_to(jnp.arange(t), (b, t))
    mask = L.causal_mask(t, t) if caches is None else None
    enc_out = enc_out.astype(dtype)

    prefill_fresh = caches is not None and t > 1

    def block(x, scanned):
        p, cache = scanned
        h, newc = L.attention(p["attn"], L.rmsnorm(p["ln1"], x), cfg,
                              positions, mask=mask,
                              cache=None if cache is None else cache["attn"],
                              prefill_fresh=prefill_fresh)
        x = x + h
        h, _ = L.attention(p["xattn"], L.rmsnorm(p["ln_x"], x), cfg,
                           positions, xa=enc_out)
        x = x + h
        x = x + L.mlp(p["mlp"], L.rmsnorm(p["ln2"], x), cfg.activation)
        return x, (None if cache is None else {"attn": newc})

    if remat and caches is None:
        block = jax.checkpoint(block,
                               policy=jax.checkpoint_policies.nothing_saveable)
    x, newc = jax.lax.scan(lambda c, s: block(c, s), x,
                           (params["dec"], caches))
    x = L.rmsnorm(params["final_norm"], x)
    if features:
        return x, newc
    return L.unembed(params, x, vocab=cfg.vocab), newc


def forward(params, tokens, cfg: ModelConfig, *, media=None,
            remat: bool = False, features: bool = False):
    """Full enc-dec training forward -> (logits, aux=0)."""
    from repro.models.transformer import cast_params
    params = cast_params(params, DTYPES[cfg.dtype])
    enc_out = encode(params, media, cfg, remat=remat)
    logits, _ = decode(params, tokens, enc_out, cfg, remat=remat,
                       features=features)
    return logits, jnp.zeros((), jnp.float32)


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=None):
    dtype = DTYPES[cfg.dtype] if dtype is None else dtype
    one = {"attn": {
        "k": jnp.zeros((batch, cfg.n_kv_heads, max_len, cfg.hd), dtype),
        "v": jnp.zeros((batch, cfg.n_kv_heads, max_len, cfg.hd), dtype),
        "pos": jnp.zeros((), jnp.int32)}}
    return jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, (cfg.n_layers,) + x.shape).copy(), one)
