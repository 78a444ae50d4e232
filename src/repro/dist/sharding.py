"""Logical-axis sharding: activation annotations and parameter placement.

Model code never names mesh axes directly. Layers tag activation dims with
*logical* names (``annotate(x, "batch", "seq", "heads", None)``) and the
launch layer binds those names to mesh axes with ``use_rules(mesh, rules)``.
Outside an active rule context ``annotate`` is the identity, so the same
model code runs single-host (tests, benches) and on the production meshes
(launch/dryrun.py) unchanged.

Parameter placement (``param_spec``) implements the standard FSDP x TP
recipe: one dimension tensor-parallel on the model axis (chosen by the
param's role — contraction inputs for down-projections, outputs
otherwise), plus one fully-sharded dimension on the data axes when sizes
divide. Divisibility is only assumed when ``axis_sizes`` is provided;
otherwise the data-axis (FSDP) placement is skipped and the caller (e.g.
the ZeRO-1 moment sharder in launch/dryrun.py) adds it.
"""

from __future__ import annotations

import contextlib
import math
from typing import Dict, Optional, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["RULES_2D", "RULES_3D", "annotate", "use_rules", "param_spec",
           "current_rules"]

Axes = Union[None, str, Tuple[str, ...]]

# logical activation/parameter dim -> mesh axes. ``None``/absent = replicated.
RULES_2D: Dict[str, Axes] = {
    "batch": ("data",),
    "seq": None,
    "embed": None,          # residual stream stays replicated (TP on heads/ff)
    "heads": ("model",),
    "kv_heads": ("model",),
    "ff": ("model",),
    "vocab": ("model",),
    "state": ("model",),
}

RULES_3D: Dict[str, Axes] = dict(RULES_2D, batch=("pod", "data"))

# active (mesh, rules) bound by use_rules(); module-level is fine — tracing
# within one context is single-threaded, and nesting restores the outer pair.
_ACTIVE: list = []


@contextlib.contextmanager
def use_rules(mesh: Mesh, rules: Dict[str, Axes]):
    """Bind logical axis names to ``mesh`` axes for annotate() calls."""
    _ACTIVE.append((mesh, rules))
    try:
        yield
    finally:
        _ACTIVE.pop()


def current_rules() -> Optional[tuple]:
    return _ACTIVE[-1] if _ACTIVE else None


def _as_tuple(axes: Axes) -> Tuple[str, ...]:
    if axes is None:
        return ()
    if isinstance(axes, str):
        return (axes,)
    return tuple(axes)


def _axes_size(mesh: Mesh, axes: Tuple[str, ...]) -> int:
    return math.prod(mesh.shape[a] for a in axes)


def annotate(x, *names):
    """Constrain ``x``'s sharding by logical dim names (identity w/o rules).

    ``names`` has one entry per dim of ``x``: a logical name from the active
    rule table or ``None`` (replicated). Names whose mesh axes do not divide
    the dim size are dropped silently — the same layer code must work for
    reduced test configs whose dims are tiny.
    """
    active = current_rules()
    if active is None:
        return x
    mesh, rules = active
    if len(names) != x.ndim:
        raise ValueError(f"annotate: {len(names)} names for rank-{x.ndim}")
    parts: list = []
    used: set = set()
    for dim, name in zip(x.shape, names):
        axes = _as_tuple(rules.get(name)) if name is not None else ()
        axes = tuple(a for a in axes if a in mesh.axis_names
                     and a not in used)
        if axes and dim % _axes_size(mesh, axes) == 0:
            used.update(axes)
            parts.append(axes if len(axes) > 1 else axes[0])
        else:
            parts.append(None)
    if all(p is None for p in parts):
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*parts)))


# ---------------------------------------------------------------------------
# Parameter placement
# ---------------------------------------------------------------------------

# params whose *input* (second-to-last) dim is the wide one: down-projections
# back into the residual stream. Everything else TPs its output dim.
_TP_IN_DIM_SUBSTRINGS = ("wo", "w2", "w_out", "down")
_REPLICATED_SUBSTRINGS = ("scale", "norm", "bias", "a_param", "decay",
                          "time_", "gate_bias")


def param_spec(name: str, shape: tuple, rules: Dict[str, Axes], *,
               axis_sizes: Optional[Dict[str, int]] = None) -> P:
    """FSDP x TP PartitionSpec for a parameter by name/shape heuristics.

    ``rules`` supplies the axis vocabulary: the model (TP) axes come from
    the ``ff`` entry, the data (FSDP) axes from ``batch``. When
    ``axis_sizes`` is given, any placement whose axes do not divide the dim
    is dropped; when absent, only the TP placement is emitted (FSDP needs a
    divisibility guarantee the caller must then add, cf. launch/dryrun).
    """
    nd = len(shape)
    if nd < 2 or any(s in name for s in _REPLICATED_SUBSTRINGS):
        return P()
    tp_axes = _as_tuple(rules.get("ff", ("model",)))
    dp_axes = _as_tuple(rules.get("batch", ("data",)))

    def fits(axes: Tuple[str, ...], dim: int) -> bool:
        if not axes:
            return False
        if axis_sizes is None:
            return True
        return dim % math.prod(axis_sizes.get(a, 1) for a in axes) == 0

    parts: list = [None] * nd
    # stacked-layer leading dim (lax.scan blocks): never shard it
    first = 1 if nd >= 3 else 0

    # tensor-parallel dim
    leaf = name.rsplit("/", 1)[-1]
    if "embed_tokens" in name:
        tp_dim = first                      # (vocab, d_model): shard vocab
    elif any(s in leaf for s in _TP_IN_DIM_SUBSTRINGS):
        tp_dim = nd - 2                     # down-proj: shard the wide input
    else:
        tp_dim = nd - 1                     # up/out-proj: shard the output
    if fits(tp_axes, shape[tp_dim]):
        parts[tp_dim] = tp_axes if len(tp_axes) > 1 else tp_axes[0]

    # FSDP dim: largest remaining dim that divides (requires axis_sizes)
    if axis_sizes is not None and dp_axes:
        cands = sorted((d for d in range(first, nd)
                        if parts[d] is None and fits(dp_axes, shape[d])),
                       key=lambda d: -shape[d])
        if cands:
            parts[cands[0]] = dp_axes if len(dp_axes) > 1 else dp_axes[0]
    return P(*parts)
