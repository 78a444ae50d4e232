"""A dense GQA decoder whose layers are all alike (phi3-medium,
starcoder2's widths): weights, reference and cost counts.

Weights: every projection is takum8 wire words, drawn per layer as a
Gaussian of standard deviation ``d_in ** -0.5`` rounded to the nearest
takum8 word (``weights.gaussian_words``); embedding and unembedding are
float32 (``weights.embeddings``); norm scales are ones. All layers come
out of one jitted call that writes one layer slice at a time into the
stacked word arrays (``lax.fori_loop``), so no float32 stack of all
layers ever exists on the device.

Reference: the published layer equations (pre-norm RMSNorm blocks,
rotary position embedding on the first and second halves of each head,
grouped-query causal softmax attention, a SwiGLU or tanh-GELU MLP, a
final RMSNorm and an untied unembedding), in float32 at
``Precision.HIGHEST``, with the seed's words regenerated here and
decoded by ``reference.decode``. The control rounds each decoded weight
matrix to int4 (symmetric, one scale per output column): the next
precision below the configuration's 8-bit weights.

Counts: every layer runs the same seven (six for GELU) projections and
attends over the whole context.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

import costs
import reference as R
import weights as W

KEYS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff",
        "vocab", "activation", "rope_base")


def model(cfg: dict) -> dict:
    """The sizes the weights, the reference and the counts use."""
    return {k: cfg["model"][k] for k in KEYS}


# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------


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


def layer_words(key, cfg: dict, layer) -> dict:
    """Takum8 words ``{leaf: [d_in, d_out] uint8}`` of one layer
    (``key`` from ``weights.base_key``; ``layer`` may be traced):
    N(0, 1/d_in) rounded to takum8."""
    out = {}
    lkey = jax.random.fold_in(key, 1 + layer)
    for i, (name, (_, din, dout)) in enumerate(
            sorted(projection_shapes(cfg).items())):
        out[name] = W.gaussian_words(jax.random.fold_in(lkey, i),
                                     (din, dout), din ** -0.5)
    return out


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
    scales."""
    from repro.kernels.ops import WireMatrix
    stacks = _build(W.base_key(seed), tuple(sorted(cfg.items())))
    emb, unemb = W.embeddings(seed, cfg)
    n, d = cfg["n_layers"], cfg["d_model"]
    block = {"ln1": {"scale": jnp.ones((n, d), jnp.float32)},
             "ln2": {"scale": jnp.ones((n, d), jnp.float32)},
             "attn": {}, "mlp": {}}
    for name, (group, _, _) in projection_shapes(cfg).items():
        block[group][name] = WireMatrix(stacks[name], fmt="takum8")
    return {"embed_tokens": emb, "unembed": unemb,
            "final_norm": {"scale": jnp.ones((d,), jnp.float32)},
            "groups": [{"b0": block}]}


# ---------------------------------------------------------------------------
# Reference
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("cfg_items",))
def block(x, n_valid, w, cfg_items):
    """One decoder layer over one sequence ``x [T, d]`` whose first
    ``n_valid`` rows are real (the rest are padding, causally after)."""
    cfg = dict(cfg_items)
    t = x.shape[0]
    h, hkv, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    pos = jnp.arange(t)
    a = R.rmsnorm(x)
    q = R.rope(R.mm(a, w["wq"]).reshape(t, h, hd), pos, cfg["rope_base"])
    k = R.rope(R.mm(a, w["wk"]).reshape(t, hkv, hd), pos, cfg["rope_base"])
    v = R.mm(a, w["wv"]).reshape(t, hkv, hd)
    x = x + R.mm(R.causal_attention(q, k, v), w["wo"])
    a = R.rmsnorm(x)
    if cfg["activation"] == "swiglu":
        hid = jax.nn.silu(R.mm(a, w["wg"])) * R.mm(a, w["w1"])
    else:
        hid = jax.nn.gelu(R.mm(a, w["w1"]), approximate=True)
    x = x + R.mm(hid, w["w2"])
    return jnp.where((pos < n_valid)[:, None], x, 0.0)


@functools.partial(jax.jit, static_argnames=("cfg_items", "control"))
def _layer_weights(layer, key, cfg_items, control):
    words = layer_words(key, dict(cfg_items), layer)
    out = {k: R.decode(v) for k, v in words.items()}
    if control:
        out = {k: R.int4_columns(v) for k, v in out.items()}
    return out


def forward_logits(seed: int, cfg: dict, seqs, rows, control: bool = False):
    """Reference logits of each sequence at its ``rows``
    (``reference.forward_logits``). ``control=True``: int4 weights."""
    items = tuple(sorted((k, v) for k, v in cfg.items()
                         if isinstance(v, (int, float, str))))
    key = W.base_key(seed)

    def layer(i, xs, lens):
        w = _layer_weights(i, key, items, control)
        return [block(x, n, w, items) for x, n in zip(xs, lens)]

    return R.forward_logits(seed, cfg, seqs, rows, layer, control)


def served_gaps(seed: int, cfg: dict, requests, control: bool = False):
    """``reference.served_gaps`` over this architecture's forward."""
    return R.served_gaps(functools.partial(forward_logits, seed, cfg),
                         requests, control)


# ---------------------------------------------------------------------------
# Counts
# ---------------------------------------------------------------------------


def projection_dims(cfg: dict):
    """``[(n_layers, [(d_in, d_out), ...])]``: every layer runs the same
    weight matmuls."""
    return [(cfg["n_layers"], [(din, dout) for _, din, dout
                               in projection_shapes(cfg).values()])]


def paged_attention(cfg: dict, contexts):
    """(flops, bytes) of decode attention over takum8 pages: each entry
    of ``contexts`` is one active row of one step attending over that
    many keys, in every layer (K and V words read once, q and the f32
    output once per row)."""
    h, hkv, hd, L = (cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"],
                     cfg["n_layers"])
    contexts = list(contexts)
    ctx = float(sum(contexts))
    n_rows = len(contexts)
    flops = 4.0 * h * hd * ctx * L
    kv = 2.0 * hkv * hd * costs.WIRE_BYTES * ctx * L
    qo = 2.0 * n_rows * h * hd * costs.OUT_BYTES * L
    return flops, kv + qo


def model_flops(cfg: dict, contexts) -> float:
    """Forward FLOPs of one token per entry of ``contexts`` (the number
    of keys it attends over): weight matmuls, attention and the
    unembedding."""
    (layers, dims), = projection_dims(cfg)
    per_tok = sum(2.0 * k * n for k, n in dims) * layers \
        + 2.0 * cfg["d_model"] * cfg["vocab"]
    att = 4.0 * cfg["n_heads"] * cfg["head_dim"] * cfg["n_layers"]
    total = 0.0
    for c in contexts:
        total += per_tok + att * c
    return total
