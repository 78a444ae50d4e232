"""kimi-k2-1t-a32b [moe]: trillion-param MoE, 384 experts top-8.
[arXiv:2501.kimi2 paper-table]"""

import dataclasses

from repro.configs.base import ArchSpec, ModelConfig, register_arch

CONFIG = ModelConfig(
    name="kimi-k2-1t-a32b",
    family="moe",
    n_layers=61,
    d_model=7_168,
    n_heads=64,
    n_kv_heads=8,
    d_ff=2_048,            # per-expert hidden
    vocab=163_840,
    head_dim=112,
    activation="swiglu",
    n_experts=384,
    top_k=8,
)

REDUCED = dataclasses.replace(
    CONFIG, n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_ff=64,
    vocab=512, head_dim=16, n_experts=16, top_k=4, dtype="f32")


@register_arch("kimi-k2-1t-a32b")
def spec() -> ArchSpec:
    return ArchSpec(CONFIG, REDUCED, "arXiv:2501.kimi2; unverified")
