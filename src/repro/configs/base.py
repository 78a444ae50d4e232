"""Config system: model / shape / runtime dataclasses + arch registry.

Every assigned architecture registers an exact ``ModelConfig`` under its
pool id (``--arch <id>``); shapes are the four assigned input-shape sets.
``reduced()`` produces the family-preserving small config used by the CPU
smoke tests (the full configs are exercised via the AOT dry-run only).
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Tuple

__all__ = ["ModelConfig", "ShapeConfig", "QuantConfig", "RuntimeConfig",
           "register_arch", "get_arch", "list_archs", "SHAPES",
           "shape_applicable", "parse_kv_quant"]


def parse_kv_quant(kv_quant: str) -> Tuple[str, int]:
    """Parse a ``ModelConfig.kv_quant`` string to ``(kind, n)``.

    One registry lookup (``repro.formats``): ``"none"`` is the identity
    codec (float cache), ``"takum<n>"`` the linear wire formats,
    ``"lns-takum<n>"`` the logarithmic ones (decode pays one exp per
    element instead of the integer reconstruction — see docs/serving.md
    for when to pick it), ``"posit<n>"`` the posit baseline. Unknown
    strings raise with the registered format names, so the error message
    can never rot behind the registry.
    """
    from repro import formats
    try:
        spec = formats.resolve(kv_quant)
    except ValueError as e:
        raise ValueError(f"unknown kv_quant {kv_quant!r}: {e}") from None
    return spec.kind, spec.n


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                  # dense | moe | hybrid_rglru | rwkv6 | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int                 # query heads (0 for attention-free)
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0            # 0 -> d_model // n_heads
    activation: str = "swiglu"   # swiglu | relu2 | gelu
    norm: str = "rmsnorm"
    rope_base: float = 10_000.0
    tie_embeddings: bool = False
    # MoE: dropless top-k routing over all ``n_experts``; this device
    # computes the experts ``[experts_first, experts_first +
    # experts_held)`` (0 held -> all of them) plus ``n_shared_experts``
    # always-on experts, each of width ``d_expert`` (0 -> ``d_ff``)
    n_experts: int = 0
    top_k: int = 0
    experts_first: int = 0
    experts_held: int = 0
    d_expert: int = 0
    n_shared_experts: int = 0
    # softmax: gates are the softmax of the top-k router logits;
    # sigmoid: top-k of sigmoid scores plus a per-expert selection bias,
    # gates the chosen scores normalised to sum 1, times routed_scaling
    router_scoring: str = "softmax"
    routed_scaling: float = 1.0
    # hybrid (RecurrentGemma): block pattern, e.g. ("rec", "rec", "attn")
    block_pattern: Tuple[str, ...] = ()
    lru_width: int = 0
    window: int = 0              # sliding-window size for local attention
    # per-layer kinds as published (empty: every layer alike):
    # "sliding_attention" (keys within ``window``) | "full_attention",
    # and "dense" | "sparse" (MoE) feed-forward
    layer_types: Tuple[str, ...] = ()
    mlp_layer_types: Tuple[str, ...] = ()
    # block equations: "pre" norms each sublayer's input, "post" its
    # output before the residual add; qk_norm RMS-normalises q and k per
    # head after projection; rope_full=False leaves full_attention
    # layers without position embedding (NoPE)
    norm_placement: str = "pre"
    norm_eps: float = 1e-6
    qk_norm: bool = False
    rope_full: bool = True
    # rwkv6
    rwkv_head_dim: int = 64
    # enc-dec
    n_enc_layers: int = 0        # encoder layers (encdec family)
    # vlm
    cross_attn_every: int = 0    # insert a cross-attn layer every k layers
    n_media_tokens: int = 1601   # stubbed frontend sequence length
    d_media: int = 0             # media embedding dim (0 -> d_model)
    # modality frontend stub: 'none' | 'vision' | 'audio'
    frontend: str = "none"
    dtype: str = "bf16"          # activation compute dtype
    param_dtype: str = "f32"
    # serving: KV-cache wire format — any repro.formats registry name
    # ('none' | 'takum<n>' | 'lns-takum<n>' | 'posit<n>')
    kv_quant: str = "none"
    # KV-sequence tile for the fused decode-attention kernel
    # (0 -> kernel default; see kernels/takum_attention.py)
    kv_block: int = 0

    def __post_init__(self):
        # JSON configuration files give lists; a frozen config hashes
        for name in ("layer_types", "mlp_layer_types", "block_pattern"):
            object.__setattr__(self, name, tuple(getattr(self, name)))
        for name in ("layer_types", "mlp_layer_types"):
            val = getattr(self, name)
            if val and len(val) != self.n_layers:
                raise ValueError(f"{name} has {len(val)} entries for "
                                 f"{self.n_layers} layers")

    @property
    def hd(self) -> int:
        return self.head_dim or (self.d_model // max(self.n_heads, 1))

    def param_count(self) -> int:
        """Total parameter count (used for 6ND model flops)."""
        d, ff, V = self.d_model, self.d_ff, self.vocab
        hd = self.hd
        attn = d * self.n_heads * hd + 2 * d * self.n_kv_heads * hd \
            + self.n_heads * hd * d
        if self.activation == "swiglu":
            mlp = 3 * d * ff
        else:
            mlp = 2 * d * ff
        if self.family == "moe":
            # every expert of the model, held here or not; the dense
            # layers of ``mlp_layer_types`` keep the plain MLP
            n_sparse = self._n_sparse()
            total = self.n_layers * (attn + 2 * d) \
                + n_sparse * self._moe_params(self.n_experts) \
                + (self.n_layers - n_sparse) * mlp
            return total + V * d * (1 if self.tie_embeddings else 2)
        per_layer = attn + mlp + 2 * d
        if self.family == "rwkv6":
            # time-mix ~ 5 d^2-ish projections + decay MLPs, channel-mix 2*d*ff
            per_layer = 5 * d * d + 2 * d * ff + 2 * d
        if self.family == "hybrid_rglru":
            n_attn = sum(1 for i in range(self.n_layers)
                         if self._block_kind(i) == "attn")
            n_rec = self.n_layers - n_attn
            rec = 3 * d * self.lru_width + 2 * self.lru_width * \
                (self.lru_width // 256 or 1)  # conv/gates approx
            attn_l = attn + mlp + 2 * d
            rec_l = rec + mlp + 2 * d
            total = n_attn * attn_l + n_rec * rec_l
            total += V * d * (1 if self.tie_embeddings else 2)
            return total
        total = self.n_layers * per_layer
        if self.family == "encdec":
            total += self.n_enc_layers * per_layer  # + cross-attn below
            total += self.n_layers * (2 * d * self.n_kv_heads * hd
                                      + d * self.n_heads * hd)
        if self.family == "vlm" and self.cross_attn_every:
            n_cross = self.n_layers // self.cross_attn_every
            total += n_cross * (attn + mlp + 2 * d)
        total += V * d * (1 if self.tie_embeddings else 2)
        return total

    def active_param_count(self) -> int:
        """Active params per token (MoE: top_k of n_experts, the shared
        experts and the router in each sparse layer)."""
        if self.family != "moe":
            return self.param_count()
        return self.param_count() - self._n_sparse() * (
            self._moe_params(self.n_experts) - self._moe_params(self.top_k))

    def _n_sparse(self) -> int:
        """MoE layers: those ``mlp_layer_types`` names sparse, else all."""
        if self.mlp_layer_types:
            return sum(t == "sparse" for t in self.mlp_layer_types)
        return self.n_layers

    def _moe_params(self, n_routed: int) -> int:
        """One MoE layer's feed-forward with ``n_routed`` routed SwiGLU
        experts, the shared experts and the router."""
        d = self.d_model
        return (n_routed + self.n_shared_experts) * 3 * d \
            * (self.d_expert or self.d_ff) + d * self.n_experts

    def _block_kind(self, i: int) -> str:
        if not self.block_pattern:
            return "attn"
        return self.block_pattern[i % len(self.block_pattern)]


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # 'train' | 'prefill' | 'decode'


SHAPES: Dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode"),
}


def shape_applicable(cfg: ModelConfig, shape: ShapeConfig) -> Tuple[bool, str]:
    """Whether an (arch, shape) cell runs; reason when skipped (DESIGN.md §5).

    long_500k requires sub-quadratic sequence mixing: only the SSM/hybrid
    families qualify; pure full-attention archs skip it.
    """
    if shape.name == "long_500k" and cfg.family not in ("rwkv6",
                                                        "hybrid_rglru"):
        return False, "long_500k skipped: pure full-attention architecture"
    return True, ""


@dataclasses.dataclass(frozen=True)
class QuantConfig:
    weights: str = "none"      # 'none' | 'takum8' | 'takum16' | 'posit16' ...
    kv_cache: str = "none"
    grad_allreduce: str = "none"   # cross-pod gradient compression
    checkpoint: str = "none"


@dataclasses.dataclass(frozen=True)
class RuntimeConfig:
    multi_pod: bool = False
    remat: str = "block"       # 'none' | 'block' (per-layer rematerialisation)
    zero1: bool = True         # shard optimizer state over data axes
    microbatch: int = 0        # 0 = no microbatching
    seq_shard: bool = True     # sequence/context parallel annotations
    quant: QuantConfig = dataclasses.field(default_factory=QuantConfig)


_REGISTRY: Dict[str, Callable[[], "ArchSpec"]] = {}


@dataclasses.dataclass(frozen=True)
class ArchSpec:
    config: ModelConfig
    reduced: ModelConfig    # small same-family config for CPU smoke tests
    source: str             # provenance string from the assignment table


def register_arch(name: str):
    def deco(fn):
        _REGISTRY[name] = fn
        return fn
    return deco


def get_arch(name: str) -> ArchSpec:
    if name not in _REGISTRY:
        # import the configs package lazily so registration side effects run
        import repro.configs  # noqa: F401
    if name not in _REGISTRY:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(_REGISTRY)}")
    return _REGISTRY[name]()


def list_archs():
    import repro.configs  # noqa: F401
    return sorted(_REGISTRY)
