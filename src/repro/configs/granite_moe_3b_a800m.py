"""granite-moe-3b-a800m [moe]: top-8 MoE.

The structured spec field says 40 experts; the inline provenance comment
says 32. The structured field wins (DESIGN.md §5 note).
[hf:ibm-granite/granite-3.0-1b-a400m-base]"""

import dataclasses

from repro.configs.base import ArchSpec, ModelConfig, register_arch

CONFIG = ModelConfig(
    name="granite-moe-3b-a800m",
    family="moe",
    n_layers=32,
    d_model=1_536,
    n_heads=24,
    n_kv_heads=8,
    d_ff=512,              # per-expert hidden
    vocab=49_155,
    head_dim=64,
    activation="swiglu",
    n_experts=40,
    top_k=8,
)

REDUCED = dataclasses.replace(
    CONFIG, n_layers=3, d_model=64, n_heads=4, n_kv_heads=2, d_ff=64,
    vocab=512, head_dim=16, n_experts=8, top_k=2, dtype="f32")


@register_arch("granite-moe-3b-a800m")
def spec() -> ArchSpec:
    return ArchSpec(CONFIG, REDUCED,
                    "hf:ibm-granite/granite-3.0-1b-a400m-base; hf")
