"""Decoder-only model assembly for the dense / moe / vlm / hybrid / rwkv6
families. Layers are stacked and scanned (compile-time friendly at 96
layers x 512 devices); heterogeneous patterns (hybrid 1-attn:2-recurrent,
vlm cross-attn every 5th layer) scan over homogeneous *super-blocks* with
any remainder unrolled.

Public surface (used by train/serve/launch):
    init(key, cfg)                          -> params
    forward(params, tokens, cfg, ...)       -> (logits, aux_loss)
    init_cache(cfg, batch, max_len, ...)    -> cache
    forward_cached(params, tokens, cfg, cache, ...) -> (logits, cache)
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.dist.sharding import annotate
from repro.models import layers as L
from repro.models import moe as moe_mod
from repro.models import rglru as rg
from repro.models import rwkv6 as rk

DTYPES = {"f32": jnp.float32, "bf16": jnp.bfloat16}


def cast_params(params, dtype):
    """Compute-dtype view of the parameters.

    * float leaves (f32 masters) are cast to the compute dtype;
    * uint8/uint16 leaves are **takum wire words** (weight-only
      quantisation, DESIGN.md §3): decoded here, at the consumer — HBM and
      any FSDP gathers along the way carry n/32 of the f32 bytes. This is
      the codec-as-matmul-input-stage integration on the XLA path (the
      Pallas kernel fuses the same decode into the matmul tile loop);
    * ``WireMatrix`` and ``ExpertWire`` nodes (serve.engine
      ``mode="wire"``) pass through untouched: their words must *stay*
      words so each ``x @ w`` site routes through the decode-once
      weight-stationary matmul, and each expert stack through the
      grouped expert matmul, instead of an eager whole-tensor decode;
    * MoE router weights and selection bias stay float32.
    """
    from repro.core import takum as _takum
    from repro.kernels.ops import ExpertWire, WireMatrix

    def is_wire(p):
        return isinstance(p, (WireMatrix, ExpertWire))

    def cast(path, p):
        if is_wire(p):
            return p
        if any(getattr(k, "key", None) in ("router", "router_bias")
               for k in path):
            return p          # MoE routing runs in float32
        if hasattr(p, "dtype"):
            if p.dtype in (jnp.uint8, jnp.uint16):
                n = jnp.iinfo(p.dtype).bits
                return _takum.takum_to_float(p, n, dtype=dtype)
            if jnp.issubdtype(p.dtype, jnp.floating):
                return p.astype(dtype)
        return p
    return jax.tree_util.tree_map_with_path(cast, params, is_leaf=is_wire)


# ---------------------------------------------------------------------------
# Block init/apply by kind
# ---------------------------------------------------------------------------


LOCAL = ":local"   # kind suffix of a sliding-window attention layer


def _base(kind: str) -> str:
    return kind.split(":")[0]


def _block_init(key, cfg: ModelConfig, kind: str, dtype):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    d = cfg.d_model
    p: Dict[str, Any] = {"ln1": L.rmsnorm_init(d), "ln2": L.rmsnorm_init(d)}
    kind = _base(kind)
    if kind in ("self", "cross"):
        p["attn"] = L.attn_init(k1, d, cfg.n_heads, cfg.n_kv_heads, cfg.hd,
                                dtype, qk_norm=cfg.qk_norm)
        p["mlp"] = L.mlp_init(k2, d, cfg.d_ff, cfg.activation, dtype)
    elif kind == "moe":
        p["attn"] = L.attn_init(k1, d, cfg.n_heads, cfg.n_kv_heads, cfg.hd,
                                dtype, qk_norm=cfg.qk_norm)
        p["moe"] = moe_mod.moe_init(
            k2, d, cfg.d_expert or cfg.d_ff, cfg.n_experts,
            held=cfg.experts_held, n_shared=cfg.n_shared_experts,
            bias=cfg.router_scoring == "sigmoid", dtype=dtype)
    elif kind == "rec":
        p["rec"] = rg.rglru_block_init(k1, d, cfg.lru_width or d, dtype)
        p["mlp"] = L.mlp_init(k2, d, cfg.d_ff, cfg.activation, dtype)
    elif kind == "rwkv":
        p = {"ln1": L.rmsnorm_init(d), "ln2": L.rmsnorm_init(d),
             "blk": rk.rwkv_block_init(k1, d, cfg.d_ff, cfg.rwkv_head_dim,
                                       dtype)}
    else:
        raise ValueError(kind)
    return p


def _block_apply(p, x, cfg: ModelConfig, kind: str, positions, *,
                 mask=None, media=None, cache=None, window=0,
                 prefill_fresh=False):
    """returns (x, aux, new_cache)."""
    aux = jnp.zeros((), jnp.float32)
    rope = kind.endswith(LOCAL) or cfg.rope_full
    kind = _base(kind)
    if kind == "rwkv":
        h, st = rk.rwkv_time_mix(p["blk"], L.rmsnorm(p["ln1"], x),
                                 cfg.rwkv_head_dim,
                                 state=None if cache is None else cache["tm"])
        x = x + h
        h, st2 = rk.rwkv_channel_mix(p["blk"], L.rmsnorm(p["ln2"], x),
                                     state=None if cache is None
                                     else cache["cm"])
        x = x + h
        newc = None if cache is None else {"tm": st, "cm": st2}
        return x, aux, newc
    if kind == "rec":
        h, st = rg.rglru_block_apply(p["rec"], L.rmsnorm(p["ln1"], x),
                                     state=None if cache is None
                                     else cache["rec"])
        x = x + h
        x = x + L.mlp(p["mlp"], L.rmsnorm(p["ln2"], x), cfg.activation)
        newc = None if cache is None else {"rec": st}
        return x, aux, newc
    if kind == "cross":
        h, _ = L.attention(p["attn"], L.rmsnorm(p["ln1"], x), cfg, positions,
                           xa=media, mask=None)
        x = x + h
        x = x + L.mlp(p["mlp"], L.rmsnorm(p["ln2"], x), cfg.activation)
        return x, aux, cache  # cross KV is position-independent; cache unused
    # self / moe: "pre" normalises each sublayer's input, "post" its
    # output before the residual add (EXAONE-4's post_attention_ and
    # post_feedforward_layernorm)
    pre = cfg.norm_placement == "pre"

    def norm(ln, h):
        return L.rmsnorm(ln, h, cfg.norm_eps)

    h, newattn = L.attention(p["attn"], norm(p["ln1"], x) if pre else x,
                             cfg, positions, mask=mask,
                             cache=None if cache is None else cache["attn"],
                             window=window, prefill_fresh=prefill_fresh,
                             rope=rope)
    x = x + (h if pre else norm(p["ln1"], h))
    hin = norm(p["ln2"], x) if pre else x
    if kind == "moe":
        h, aux, stats = moe_mod.moe_apply(p["moe"], hin, cfg)
    else:
        # thread the attn cache dict through the MLP's TP seam so its
        # error-feedback residual (tp_res_m) rides the same scan carry
        h, newattn = L.mlp_tp(p["mlp"], hin, cfg.activation, newattn)
    x = x + (h if pre else norm(p["ln2"], h))
    newc = None if cache is None else {"attn": newattn}
    if cache is not None and "moe" in cache:
        # the paged cache's per-layer routing counters of this step
        newc["moe"] = jnp.stack([stats["moe_rows"], stats["moe_calls"]])
    return x, aux, newc


# ---------------------------------------------------------------------------
# Layer plan: (kind, count) groups that scan homogeneously
# ---------------------------------------------------------------------------


def layer_plan(cfg: ModelConfig):
    """Returns a list of (scan_kinds: tuple, n_repeat). Each group is a
    super-block of len(scan_kinds) layers, repeated n_repeat times by scan."""
    if cfg.layer_types or cfg.mlp_layer_types:
        return _typed_plan(cfg)
    if cfg.family == "rwkv6":
        return [(("rwkv",), cfg.n_layers)]
    if cfg.family == "hybrid_rglru":
        pat = cfg.block_pattern or ("rec", "rec", "attn")
        pat = tuple("self" if k == "attn" else k for k in pat)
        n_full = cfg.n_layers // len(pat)
        plan = [(pat, n_full)]
        rem = cfg.n_layers % len(pat)
        if rem:
            plan.append((pat[:rem], 1))
        return plan
    if cfg.family == "vlm" and cfg.cross_attn_every:
        k = cfg.cross_attn_every
        pat = ("cross",) + ("self",) * (k - 1)
        assert cfg.n_layers % k == 0
        return [(pat, cfg.n_layers // k)]
    kind = "moe" if cfg.family == "moe" else "self"
    return [((kind,), cfg.n_layers)]


def _typed_plan(cfg: ModelConfig):
    """The plan of published per-layer types: each layer is ``"moe"``
    (``mlp_layer_types`` "sparse") or ``"self"`` (dense), with
    :data:`LOCAL` for ``layer_types`` "sliding_attention". A run of
    layers with one feed-forward type is scanned over whole periods of
    the attention pattern (the smallest ``p`` with ``type[i] ==
    type[i % p]``, aligned to layer 0), with the layers before the
    run's first period boundary and after its last whole period as
    groups of their own: K-EXAONE's layers 0-7 are dense ``L``, then
    MoE ``L, L, G``, then MoE ``L, L, L, G``."""
    n = cfg.n_layers
    attn = cfg.layer_types or ("full_attention",) * n
    mlp = cfg.mlp_layer_types or \
        (("sparse" if cfg.family == "moe" else "dense"),) * n
    kinds = [("moe" if m == "sparse" else "self")
             + (LOCAL if a == "sliding_attention" else "")
             for a, m in zip(attn, mlp)]
    period = next(p for p in range(1, n + 1)
                  if all(attn[i] == attn[i % p] for i in range(n)))
    plan, a = [], 0
    while a < n:
        b = a
        while b < n and mlp[b] == mlp[a]:
            b += 1
        head = min(b, -(-a // period) * period)
        reps = (b - head) // period
        tail = head + reps * period
        for lo, hi, rep in ((a, head, 1), (head, head + period, reps),
                            (tail, b, 1)):
            if hi > lo and rep:
                plan.append((tuple(kinds[lo:hi]), rep))
        a = b
    return plan


def _window_for(cfg: ModelConfig, kind: str) -> int:
    if kind.endswith(LOCAL) or (cfg.family == "hybrid_rglru"
                                and kind == "self"):
        return cfg.window
    return 0


# ---------------------------------------------------------------------------
# init / forward
# ---------------------------------------------------------------------------


def init(key, cfg: ModelConfig):
    dtype = DTYPES[cfg.param_dtype]
    keys = jax.random.split(key, 8)
    params: Dict[str, Any] = L.embed_init(keys[0], cfg.vocab, cfg.d_model,
                                          cfg.tie_embeddings, dtype)
    params["final_norm"] = L.rmsnorm_init(cfg.d_model)
    if cfg.family == "vlm" and cfg.d_media and cfg.d_media != cfg.d_model:
        params["embed_media"] = L.dense_init(keys[1], cfg.d_media,
                                             cfg.d_model, dtype=dtype)
    groups = []
    for gi, (pat, n_rep) in enumerate(layer_plan(cfg)):
        gkey = jax.random.fold_in(keys[2], gi)

        def one(k):
            ks = jax.random.split(k, len(pat))
            return {f"b{i}": _block_init(ks[i], cfg, pat[i], dtype)
                    for i in range(len(pat))}

        stack = jax.vmap(one)(jax.random.split(gkey, n_rep))
        groups.append(stack)
    params["groups"] = groups
    return params


def _run_groups(params, x, cfg, positions, *, mask, media, caches, remat,
                windows_needed=True, prefill_fresh=False):
    """Scan each (super-block) group; returns (x, aux_total, new_caches).
    ``mask`` is ``None`` or a function of a layer's window."""
    aux_total = jnp.zeros((), jnp.float32)
    new_caches = []
    for gi, (pat, n_rep) in enumerate(layer_plan(cfg)):
        stack = params["groups"][gi]
        gcache = None if caches is None else caches[gi]

        def superblock(x, scanned, pat=pat):
            bparams, bcache = scanned
            aux_sb = jnp.zeros((), jnp.float32)
            newc = {}
            for i, kind in enumerate(pat):
                window = _window_for(cfg, kind)
                x, aux_i, nc = _block_apply(
                    bparams[f"b{i}"], x, cfg, kind, positions,
                    mask=None if mask is None else mask(window),
                    media=media,
                    cache=None if bcache is None else bcache[f"b{i}"],
                    window=window, prefill_fresh=prefill_fresh)
                aux_sb = aux_sb + aux_i
                if nc is not None:
                    newc[f"b{i}"] = nc
            return x, aux_sb, (newc if newc else None)

        if remat:
            superblock = jax.checkpoint(
                superblock, policy=jax.checkpoint_policies.nothing_saveable)

        def scan_fn(carry, scanned):
            x, aux = carry
            x, aux_sb, newc = superblock(x, scanned)
            return (x, aux + aux_sb), newc

        (x, aux_total), newc_stack = jax.lax.scan(
            scan_fn, (x, aux_total), (stack, gcache))
        new_caches.append(newc_stack)
    return x, aux_total, (new_caches if caches is not None else None)


def _prep_media(params, media, dtype):
    if media is None:
        return None
    media = media.astype(dtype)
    if "embed_media" in params:
        media = media @ params["embed_media"]
    return annotate(media, "batch", None, "embed")


def _pad_for_rwkv(cfg, tokens):
    if cfg.family != "rwkv6":
        return tokens, tokens.shape[1]
    t = tokens.shape[1]
    pad = -t % rk.CHUNK
    if pad:
        tokens = jnp.pad(tokens, ((0, 0), (0, pad)))
    return tokens, t


def forward(params, tokens, cfg: ModelConfig, *, media=None,
            remat: bool = False, features: bool = False):
    """Training/eval forward: tokens [B, T] -> (logits [B, T, V], aux).
    ``features=True`` returns the final-norm hidden states instead of
    logits (the chunked-xent loss unembeds per chunk)."""
    dtype = DTYPES[cfg.dtype]
    params = cast_params(params, dtype)
    tokens, t_orig = _pad_for_rwkv(cfg, tokens)
    b, t = tokens.shape
    x = L.embed(params, tokens, dtype)
    positions = jnp.broadcast_to(jnp.arange(t), (b, t))
    mask = None
    if cfg.family not in ("rwkv6",) and t < L.ATTN_CHUNK_T:
        # long sequences use the chunked path (builds its own band masks);
        # materialising a [T, T] mask at 32k+ would itself blow memory
        def mask(window):
            return L.causal_mask(t, t, window=window)
    media = _prep_media(params, media, dtype)
    x, aux, _ = _run_groups(params, x, cfg, positions, mask=mask,
                            media=media, caches=None, remat=remat)
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    if features:
        return x[:, :t_orig], aux
    logits = L.unembed(params, x, vocab=cfg.vocab)
    return logits[:, :t_orig], aux


# ---------------------------------------------------------------------------
# KV-cache / recurrent-state serving path
# ---------------------------------------------------------------------------


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=None, start=None) -> list:
    """Stacked caches matching the layer plan (leading dim = scan length)."""
    dtype = DTYPES[cfg.dtype] if dtype is None else dtype
    from repro import formats
    kv_spec = formats.resolve(cfg.kv_quant)
    # wire caches store raw words; the identity codec stays in `dtype`
    kv_dtype = kv_spec.word_dtype or dtype
    caches = []
    for pat, n_rep in layer_plan(cfg):
        def one_cache():
            c = {}
            for i, kind in enumerate(pat):
                kind = _base(kind)
                if kind in ("self", "moe"):
                    # head-major [B, Hkv, T, hd]: a KV tile of one head
                    # is a contiguous (T, hd) slab for the fused kernel
                    attn = {
                        "k": jnp.zeros((batch, cfg.n_kv_heads, max_len,
                                        cfg.hd), kv_dtype),
                        "v": jnp.zeros((batch, cfg.n_kv_heads, max_len,
                                        cfg.hd), kv_dtype),
                        "pos": jnp.zeros((), jnp.int32),
                    }
                    if start is not None:
                        # per-sequence first-valid position (left-padded
                        # prompts must not attend to their padding)
                        attn["start"] = jnp.asarray(start, jnp.int32)
                    c[f"b{i}"] = {"attn": attn}
                elif kind == "rec":
                    c[f"b{i}"] = {"rec": rg.rglru_decode_state(
                        batch, cfg.lru_width or cfg.d_model, dtype)}
                elif kind == "rwkv":
                    st = rk.rwkv_decode_state(batch, cfg.d_model,
                                              cfg.rwkv_head_dim, dtype)
                    c[f"b{i}"] = {"tm": {"S": st["S"],
                                         "tm_prev": st["tm_prev"]},
                                  "cm": {"cm_prev": st["cm_prev"]}}
                elif kind == "cross":
                    c[f"b{i}"] = {}
            return c

        stacked = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x, (n_rep,) + x.shape).copy()
            if hasattr(x, "shape") else x,
            one_cache())
        caches.append(stacked)
    return caches


def paged_supported(cfg: ModelConfig) -> bool:
    """Whether ``cfg``'s layer plan can run on the paged KV cache.

    Paging applies to attention state only: every block must be a
    ``self``/``moe`` attention block. Recurrent families (rwkv6, hybrid
    rglru) carry O(1)-per-sequence state — there is nothing to page —
    and cross-attention / encdec layers hold position-independent or
    encoder state outside the paged pool's layout.
    """
    if cfg.family == "encdec":
        return False
    return all(_base(kind) in ("self", "moe")
               for pat, _ in layer_plan(cfg) for kind in pat)


def init_paged_cache(cfg: ModelConfig, batch: int, num_pages: int,
                     page_size: int, max_pages: int, dtype=None) -> list:
    """Paged decode cache: one ``[num_pages, Hkv, page_size, hd]``
    wire-word pool per layer (stacked per scan group, like
    :func:`init_cache`) plus per-sequence block tables.

    Unlike the contiguous cache there is no batch dimension on K/V —
    capacity is the *pool*, shared by whoever is scheduled: ``table``
    ``[batch, max_pages]`` maps each decode-batch slot's kk-th KV block
    to a page, ``pos``/``start`` are per-slot vectors. Page 0 is
    reserved by the allocator (``serve.paged.PagePool``) as the scratch
    page idle slots point at. The table/pos/start leaves are replicated
    per layer so the stacked cache scans homogeneously; the serving
    layer keeps them in sync across layers.
    """
    dtype = DTYPES[cfg.dtype] if dtype is None else dtype
    if not paged_supported(cfg):
        raise ValueError(
            f"paged KV cache requires an attention-only layer plan; "
            f"family {cfg.family!r} has non-attention state (use the "
            "contiguous init_cache)")
    from repro import formats
    kv_spec = formats.resolve(cfg.kv_quant)
    kv_dtype = kv_spec.word_dtype or dtype
    caches = []
    for pat, n_rep in layer_plan(cfg):
        def one_cache():
            c = {}
            for i, kind in enumerate(pat):
                if _base(kind) == "moe":
                    # routed rows and expert calls of the last step
                    # (summed by the scheduler, PageStats.moe_rows)
                    c[f"b{i}"] = {"moe": jnp.zeros((2,), jnp.int32)}
                else:
                    c[f"b{i}"] = {}
                c[f"b{i}"]["attn"] = {
                    "k": jnp.zeros((num_pages, cfg.n_kv_heads, page_size,
                                    cfg.hd), kv_dtype),
                    "v": jnp.zeros((num_pages, cfg.n_kv_heads, page_size,
                                    cfg.hd), kv_dtype),
                    "table": jnp.zeros((batch, max_pages), jnp.int32),
                    "pos": jnp.zeros((batch,), jnp.int32),
                    "start": jnp.zeros((batch,), jnp.int32),
                }
            return c

        stacked = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x, (n_rep,) + x.shape).copy()
            if hasattr(x, "shape") else x,
            one_cache())
        caches.append(stacked)
    return caches


def forward_cached(params, tokens, cfg: ModelConfig, caches, *, pos,
                   media=None, last_only: bool = False):
    """Prefill (T > 1) or decode (T == 1) with state. Returns
    (logits [B, T_eff, V], new_caches). ``pos`` is the position of
    tokens[:, 0]. ``last_only`` unembeds just the final position —
    prefill never needs the other 32k x vocab logits (at 256k vocab
    that is ~16 GB/device of avoided traffic)."""
    dtype = DTYPES[cfg.dtype]
    params = cast_params(params, dtype)
    b, t = tokens.shape
    if cfg.family == "rwkv6" and t > 1:
        # stateful prefill must not pollute the carried state with padding
        assert t % rk.CHUNK == 0, \
            f"rwkv6 prefill length must be a multiple of {rk.CHUNK}"
    t_orig = t
    x = L.embed(params, tokens, dtype)
    positions = pos + jnp.broadcast_to(jnp.arange(t), (b, t))
    media = _prep_media(params, media, dtype)
    # t > 1 with a cache means a fresh (pos==0) prefill in our serving
    # flows; the chunked-attention fast path relies on that invariant
    x, _, new_caches = _run_groups(params, x, cfg, positions, mask=None,
                                   media=media, caches=caches, remat=False,
                                   prefill_fresh=t > 1)
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    if last_only:
        logits = L.unembed(params, x[:, t_orig - 1:t_orig], vocab=cfg.vocab)
    else:
        logits = L.unembed(params, x[:, :t_orig], vocab=cfg.vocab)
    return logits, new_caches
