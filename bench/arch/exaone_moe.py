"""K-EXAONE's decoder (``exaone_moe``): sliding-window and full GQA
layers with QK-norm and post-sublayer norms, a dense first layer, then
sigmoid-routed experts with a shared expert; weights, reference and
cost counts for one chip's expert-parallel share.

Layer equations: attention and norms as EXAONE-4
(``transformers/models/exaone4/modeling_exaone4.py``): q and k are
RMS-normalised over each head right after projection; rotary position
embedding only on ``sliding_attention`` layers (full layers are NoPE);
no pre-norm, but an RMSNorm on each sublayer's output before the
residual add. Router, routed and shared experts as DeepSeek-V3
(``DeepseekV3TopkRouter``, ``DeepseekV3MoE``): ``s = sigmoid(x W_r)``
in float32 over all routed experts, the top-k of ``s + b`` chosen (``b``
a per-expert selection bias used for the choice only), gates ``s_sel /
sum(s_sel) * routed_scaling``, ``y = shared(x) + sum_i g_i E_i(x)``,
every expert a SwiGLU.

The share: this chip holds experts ``[experts_first, experts_first +
experts_held)`` of each expert layer and computes only their terms; the
normalisation still counts all chosen experts. The vocabulary is the
configuration's slice.

Weights: every projection, expert and shared-expert matrix is takum8
words of N(0, 1/d_in) (``weights.gaussian_words``), each expert's from a
key of its global id, so a share is a slice of the whole layer; the
router N(0, 1/d_model) and the selection bias N(0, ``BIAS_STD``^2) are
float32; embedding and unembedding float32 (``weights.embeddings``);
norm scales ones. Each layer's words come from one jitted call per
layer and are written into the stacks the program's layer plan scans,
so no float32 copy of a weight ever exists on the device.

Reference: those equations in float32 at ``Precision.HIGHEST``,
regenerating and decoding each layer's words (``reference.decode``), one
layer at a time, the held experts one expert at a time; keys and values
pass through the configuration's takum8 KV words (:func:`kv_round`, from
the format's definition), as the pages store them; it imports nothing of
the program. The control rounds each decoded weight matrix
(and the unembedding) to int4 per output column, as ``dense_gqa``'s.

Counts: attention projections, the dense MLP and the shared expert run
through ``quant_matmul``; the routed experts through ``expert_matmul``;
sliding layers attend over at most ``window`` keys.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

import costs
import reference as R
import takum8
import weights as W

BIAS_STD = 0.05   # the selection bias's spread (assumed; learned in the model)
KEYS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff",
        "d_expert", "n_experts", "experts_first", "experts_held", "top_k",
        "n_shared_experts", "routed_scaling", "vocab", "rope_base", "window",
        "norm_eps", "kv_quant")
SLIDING = "sliding_attention"
SEQ_BUCKET = 2048     # the reference pads sequences to a multiple of this


def model(cfg: dict) -> dict:
    """The sizes the weights, the reference and the counts use, from the
    configuration's ``model`` group (the program's ``ModelConfig``)."""
    m = cfg["model"]
    out = {k: m[k] for k in KEYS}
    out["layer_types"] = tuple(m["layer_types"])
    out["mlp_layer_types"] = tuple(m["mlp_layer_types"])
    out["program"] = dict(m)     # build_params lays the tree out by it
    return out


def _sparse(cfg: dict, layer: int) -> bool:
    return cfg["mlp_layer_types"][layer] == "sparse"


# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------


def word_shapes(cfg: dict, sparse: bool) -> dict:
    """``{leaf: shape}`` of one layer's takum8 words; expert stacks
    ``[held, d_in, d_out]``."""
    d, h, hkv, hd = (cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"],
                     cfg["head_dim"])
    shapes = {"wq": (d, h * hd), "wk": (d, hkv * hd), "wv": (d, hkv * hd),
              "wo": (h * hd, d)}
    if not sparse:
        ff = cfg["d_ff"]
        shapes.update(wg=(d, ff), w1=(d, ff), w2=(ff, d))
        return shapes
    f, e = cfg["d_expert"], cfg["experts_held"]
    fs = f * cfg["n_shared_experts"]
    shapes.update(shared_wg=(d, fs), shared_w1=(d, fs), shared_w2=(fs, d),
                  experts_wg=(e, d, f), experts_w1=(e, d, f),
                  experts_w2=(e, f, d))
    return shapes


def layer_words(key, cfg: dict, layer: int, sparse: bool) -> dict:
    """Takum8 words of one layer (``layer`` may be traced): N(0, 1/d_in);
    expert ``j`` of a stack from the key of its global id."""
    out = {}
    lkey = jax.random.fold_in(key, 1 + layer)
    for i, (name, shape) in enumerate(sorted(word_shapes(cfg,
                                                         sparse).items())):
        k = jax.random.fold_in(lkey, i)
        if name.startswith("experts_"):
            ids = cfg["experts_first"] + jnp.arange(shape[0])
            out[name] = jax.vmap(lambda e: W.gaussian_words(
                jax.random.fold_in(k, e), shape[1:], shape[1] ** -0.5))(ids)
        else:
            out[name] = W.gaussian_words(k, shape, shape[0] ** -0.5)
    return out


def router(key, cfg: dict, layer: int):
    """(router [d, n_experts], selection bias [n_experts]) float32 of a
    sparse layer, over every routed expert."""
    kr, kb = jax.random.split(jax.random.fold_in(
        jax.random.fold_in(key, 1 + layer), 1000))
    d, e = cfg["d_model"], cfg["n_experts"]
    return (jax.random.normal(kr, (d, e), jnp.float32) * d ** -0.5,
            jax.random.normal(kb, (e,), jnp.float32) * BIAS_STD)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _build(key, cfg_items, layers, sparse):
    """The word stacks ``[len(layers), ...]`` of layers that share a
    place in the plan, one layer written at a time."""
    cfg = dict(cfg_items)
    n = len(layers)
    ids = jnp.asarray(layers, jnp.int32)
    shapes = word_shapes(cfg, sparse)
    stacks = {name: jnp.zeros((n,) + s, jnp.uint8)
              for name, s in shapes.items()}
    if sparse:
        d, e = cfg["d_model"], cfg["n_experts"]
        stacks["router"] = jnp.zeros((n, d, e), jnp.float32)
        stacks["router_bias"] = jnp.zeros((n, e), jnp.float32)

    def body(r, stacks):
        layer = ids[r]
        words = layer_words(key, cfg, layer, sparse)
        if sparse:
            words["router"], words["router_bias"] = router(key, cfg, layer)
        return {name: jax.lax.dynamic_update_index_in_dim(
                    stacks[name], words[name], r, 0) for name in stacks}

    return jax.lax.fori_loop(0, n, body, stacks)


def _block_tree(stacks: dict, n: int, cfg: dict, sparse: bool) -> dict:
    """One place of the plan in the program's layout."""
    from repro.kernels.ops import ExpertWire, WireMatrix
    d, hd = cfg["d_model"], cfg["head_dim"]

    def wire(name):
        return WireMatrix(stacks[name], fmt="takum8")

    ones = {"scale": jnp.ones((n, d), jnp.float32)}
    block = {"ln1": ones, "ln2": dict(ones),
             "attn": {k: wire(k) for k in ("wq", "wk", "wv", "wo")}}
    block["attn"]["q_norm"] = {"scale": jnp.ones((n, hd), jnp.float32)}
    block["attn"]["k_norm"] = {"scale": jnp.ones((n, hd), jnp.float32)}
    if not sparse:
        block["mlp"] = {k: wire(k) for k in ("wg", "w1", "w2")}
        return block
    block["moe"] = {
        "router": stacks["router"], "router_bias": stacks["router_bias"],
        "shared": {k: wire(f"shared_{k}") for k in ("wg", "w1", "w2")}}
    for k in ("experts_wg", "experts_w1", "experts_w2"):
        block["moe"][k] = ExpertWire(stacks[k], fmt="takum8")
    return block


def build_params(seed: int, cfg: dict):
    """The served tree in the layout of the program's layer plan:
    ``WireMatrix`` takum8 words for attention, the dense MLP and the
    shared expert, ``ExpertWire`` stacks of the held experts, float32
    router and bias, embedding and unembedding, unit norm scales."""
    from repro.configs.base import ModelConfig
    from repro.models.transformer import layer_plan
    mcfg = ModelConfig(**cfg["program"])
    key = W.base_key(seed)
    items = _items(cfg)
    groups, start = [], 0
    for pat, n_rep in layer_plan(mcfg):
        group = {}
        for b in range(len(pat)):
            layers = tuple(start + r * len(pat) + b for r in range(n_rep))
            sparse = _sparse(cfg, layers[0])
            stacks = _build(key, items, layers, sparse)
            group[f"b{b}"] = _block_tree(stacks, n_rep, cfg, sparse)
        groups.append(group)
        start += len(pat) * n_rep
    emb, unemb = W.embeddings(seed, cfg)
    return {"embed_tokens": emb, "unembed": unemb,
            "final_norm": {"scale": jnp.ones((cfg["d_model"],),
                                             jnp.float32)},
            "groups": groups}


# ---------------------------------------------------------------------------
# Reference
# ---------------------------------------------------------------------------


def _rmsnorm(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _attention(q, k, v, window: int):
    """``reference.causal_attention`` with keys limited to the last
    ``window`` positions (0: all): query ``i`` attends key ``j`` iff
    ``i - window < j <= i``."""
    t, h, hd = q.shape
    g = h // k.shape[1]
    pos = jnp.arange(t)
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)

    def attend(qb, q0):
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=R.HI) / math.sqrt(hd)
        qpos = q0 + jnp.arange(qb.shape[0])
        mask = pos[None, :] <= qpos[:, None]
        if window:
            mask = mask & (pos[None, :] > qpos[:, None] - window)
        s = jnp.where(mask[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=R.HI)

    nb = t // R.BUCKET
    qs = q.reshape(nb, R.BUCKET, h, hd)
    return jax.lax.map(lambda a: attend(*a),
                       (qs, jnp.arange(nb) * R.BUCKET)).reshape(t, h * hd)


def _takum_value(w: int, n: int) -> float:
    """Linear takum of ``n`` bits, positive words: ``takum8.value``'s
    definition with ``n - 5`` bits after the regime."""
    d = (w >> (n - 2)) & 1
    reg = (w >> (n - 5)) & 7
    r = reg if d else 7 - reg
    t = n - 5
    tail = w & ((1 << t) - 1)
    if r <= t:
        p = t - r
        c_bits, m = tail >> p, tail & ((1 << p) - 1)
    else:
        p, c_bits, m = 0, tail << (r - t), 0
    c = (2 ** r - 1 + c_bits) if d else (-2 ** (r + 1) + 1 + c_bits)
    return (1 + m / 2 ** p) * 2.0 ** c


@functools.lru_cache(maxsize=None)
def _kv_grid():
    """The positive takum8 values in word order, and the rounding
    boundary between words ``w`` and ``w + 1``: the value of the 9-bit
    word ``2w + 1``, halfway between them in the encoding."""
    with np.errstate(over="ignore"):     # words beyond float32: inf
        vals = np.array([takum8.value(w) for w in range(128)], np.float32)
        mids = np.array([_takum_value(2 * w + 1, 9) for w in range(127)],
                        np.float32)
    return vals, mids


def kv_round(x):
    """``x`` stored as takum8 KV words and read back: takum rounding to
    nearest in the encoding (a value on a boundary takes the lower
    magnitude, where the format rounds to the even word), saturating at
    the largest finite word, never rounding a nonzero value to zero."""
    vals, mids = (jnp.asarray(a) for a in _kv_grid())
    a = jnp.abs(x)
    j = jnp.clip(jnp.searchsorted(mids, a, side="left"), 1, 127)
    return jnp.where(a == 0, 0.0, jnp.sign(x) * vals[j])


def _swiglu(x, wg, w1, w2):
    return R.mm(jax.nn.silu(R.mm(x, wg)) * R.mm(x, w1), w2)


def routed(x, w, cfg: dict, control: bool = False):
    """The held experts' part of a sparse layer for ``x [T, d]``: the
    router over all experts, then each held expert's SwiGLU (decoded one
    expert at a time) weighted by its gate where it was chosen."""
    s = jax.nn.sigmoid(R.mm(x, w["router"]))
    _, idx = jax.lax.top_k(s + w["router_bias"], cfg["top_k"])
    g = jnp.take_along_axis(s, idx, axis=-1)
    g = g / (jnp.sum(g, axis=-1, keepdims=True) + 1e-20) \
        * cfg["routed_scaling"]

    def one(acc, e):
        mats = [R.decode(w[k][e]) for k in ("experts_wg", "experts_w1",
                                             "experts_w2")]
        if control:
            mats = [R.int4_columns(m) for m in mats]
        gate = jnp.sum(jnp.where(idx == cfg["experts_first"] + e, g, 0.0),
                       axis=-1)
        return acc + gate[:, None] * _swiglu(x, *mats), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        jnp.arange(cfg["experts_held"]))
    return y


@functools.partial(jax.jit, static_argnames=("cfg_items", "sliding",
                                             "sparse", "control"))
def block(x, n_valid, w, cfg_items, sliding, sparse, control=False):
    """One layer over one sequence ``x [T, d]`` whose first ``n_valid``
    rows are real."""
    cfg = dict(cfg_items)
    t = x.shape[0]
    h, hkv, hd, eps = (cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"],
                       cfg["norm_eps"])
    pos = jnp.arange(t)
    q = _rmsnorm(R.mm(x, w["wq"]).reshape(t, h, hd), eps)
    k = _rmsnorm(R.mm(x, w["wk"]).reshape(t, hkv, hd), eps)
    v = R.mm(x, w["wv"]).reshape(t, hkv, hd)
    if sliding:
        q, k = R.rope(q, pos, cfg["rope_base"]), R.rope(k, pos,
                                                       cfg["rope_base"])
    if cfg["kv_quant"] == "takum8":     # what the KV pages hold
        k, v = kv_round(k), kv_round(v)
    a = R.mm(_attention(q, k, v, cfg["window"] if sliding else 0), w["wo"])
    x = x + _rmsnorm(a, eps)
    if sparse:
        m = routed(x, w, cfg, control) + _swiglu(
            x, w["shared_wg"], w["shared_w1"], w["shared_w2"])
    else:
        m = _swiglu(x, w["wg"], w["w1"], w["w2"])
    x = x + _rmsnorm(m, eps)
    return jnp.where((pos < n_valid)[:, None], x, 0.0)


@functools.partial(jax.jit, static_argnames=("cfg_items", "sparse",
                                             "control"))
def _layer_weights(layer, key, cfg_items, sparse, control):
    """One layer's weights for the reference: dense matrices decoded
    (int4-rounded for the control), expert stacks left as words for
    :func:`routed` to decode one expert at a time."""
    cfg = dict(cfg_items)
    words = layer_words(key, cfg, layer, sparse)
    out = {}
    for k, v in words.items():
        if k.startswith("experts_"):
            out[k] = v
            continue
        out[k] = R.decode(v)
        if control:
            out[k] = R.int4_columns(out[k])
    if sparse:
        out["router"], out["router_bias"] = router(key, cfg, layer)
    return out


def _items(cfg: dict):
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str))))


def forward_logits(seed: int, cfg: dict, seqs, rows, control: bool = False):
    """Reference logits of each sequence at its ``rows`` (float32 numpy
    ``[len(rows_i), vocab]``), layer by layer as
    ``reference.forward_logits``, with the final norm's ``norm_eps``."""
    items = _items(cfg)
    key = W.base_key(seed)
    emb, unemb = W.embeddings(seed, cfg)
    if control:
        unemb = R.int4_columns(unemb)
    # one padded length for every sequence, so each kind of layer
    # compiles once (the sequences are causal: padding comes after)
    t = -(-max(len(s) for s in seqs) // SEQ_BUCKET) * SEQ_BUCKET
    xs, lens = [], []
    for s in seqs:
        tok = np.zeros(t, np.int32)
        tok[:len(s)] = s
        xs.append(emb[jnp.asarray(tok)])
        lens.append(len(s))
    for i in range(cfg["n_layers"]):
        sparse = _sparse(cfg, i)
        w = _layer_weights(i, key, items, sparse, control)
        sliding = cfg["layer_types"][i] == SLIDING
        xs = [block(x, n, w, items, sliding, sparse, control)
              for x, n in zip(xs, lens)]
    out = []
    for x, r in zip(xs, rows):
        n = len(r)
        padded = np.full(-(-n // R.ROWS) * R.ROWS, r[-1], np.int32)
        padded[:n] = r
        logits = _head(x, jnp.asarray(padded), unemb, cfg["vocab"],
                       cfg["norm_eps"])
        out.append(np.asarray(logits)[:n])
    return out


@functools.partial(jax.jit, static_argnames=("vocab", "eps"))
def _head(x, rows, unembed, vocab, eps):
    return R.mm(_rmsnorm(x[rows], eps), unembed[:, :vocab])


def served_gaps(seed: int, cfg: dict, requests, control: bool = False):
    """``reference.served_gaps`` over this architecture's forward."""
    return R.served_gaps(functools.partial(forward_logits, seed, cfg),
                         requests, control)


# ---------------------------------------------------------------------------
# Counts
# ---------------------------------------------------------------------------


def _n_sparse(cfg: dict) -> int:
    return sum(_sparse(cfg, i) for i in range(cfg["n_layers"]))


def projection_dims(cfg: dict):
    """``[(layers, [(d_in, d_out), ...])]`` of the ``quant_matmul``
    calls: attention in every layer, the dense MLP in the dense layers,
    the shared expert in the sparse ones (the routed experts run through
    ``expert_matmul``: :func:`expert_matmul`)."""
    d, h, hkv, hd = (cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"],
                     cfg["head_dim"])
    ff, fs = cfg["d_ff"], cfg["d_expert"] * cfg["n_shared_experts"]
    n_sparse = _n_sparse(cfg)
    out = [(cfg["n_layers"], [(d, h * hd), (d, hkv * hd), (d, hkv * hd),
                              (h * hd, d)])]
    if cfg["n_layers"] - n_sparse:
        out.append((cfg["n_layers"] - n_sparse, [(d, ff), (d, ff), (ff, d)]))
    if n_sparse:
        out.append((n_sparse, [(d, fs), (d, fs), (fs, d)]))
    return out


def _keys(cfg: dict, layer: int, ctx: float) -> float:
    if cfg["layer_types"][layer] == SLIDING:
        return min(ctx, cfg["window"])
    return ctx


def paged_attention(cfg: dict, contexts):
    """(flops, bytes) of decode attention over takum8 pages: each entry
    of ``contexts`` is one active row of one step; a sliding layer reads
    the K and V words of at most ``window`` keys, a full layer of all
    (q and the f32 output once per row and layer)."""
    h, hkv, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    contexts = list(contexts)
    keys = sum(_keys(cfg, i, c) for c in contexts
               for i in range(cfg["n_layers"]))
    flops = 4.0 * h * hd * keys
    kv = 2.0 * hkv * hd * costs.WIRE_BYTES * keys
    qo = 2.0 * len(contexts) * h * hd * costs.OUT_BYTES * cfg["n_layers"]
    return flops, kv + qo


def model_flops(cfg: dict, contexts) -> float:
    """Forward FLOPs of one token per entry of ``contexts`` (the keys it
    attends over) on this chip's share: the ``quant_matmul`` projections,
    the router over every expert, ``top_k * held / n_experts`` routed
    experts a token in each sparse layer, attention and the unembedding."""
    d, f = cfg["d_model"], cfg["d_expert"]
    per_tok = sum(2.0 * k * n * layers for layers, dims
                  in projection_dims(cfg) for k, n in dims)
    n_sparse = _n_sparse(cfg)
    share = cfg["top_k"] * cfg["experts_held"] / cfg["n_experts"]
    per_tok += n_sparse * (2.0 * d * cfg["n_experts"]
                           + share * 3 * 2.0 * d * f)
    per_tok += 2.0 * d * cfg["vocab"]
    att = 4.0 * cfg["n_heads"] * cfg["head_dim"]
    total = 0.0
    for c in contexts:
        total += per_tok + att * sum(_keys(cfg, i, c)
                                     for i in range(cfg["n_layers"]))
    return total


def expert_matmul(cfg: dict, rows_per_expert):
    """(flops, bytes) of the grouped expert matmuls: each entry of
    ``rows_per_expert`` is one held expert's rows in one layer of one
    step (a call), which reads its three matrices' words once and its
    rows' activations (``costs.qmm`` for the gate, up and down
    projections)."""
    d, f = cfg["d_model"], cfg["d_expert"]
    flops = nbytes = 0.0
    for r in rows_per_expert:
        for k, n in ((d, f), (d, f), (f, d)):
            fl, by = costs.qmm(r, k, n)
            flops, nbytes = flops + fl, nbytes + by
    return flops, nbytes
