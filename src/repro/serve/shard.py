"""Multi-device serving: the shard plan and the sharded step functions.

:class:`ShardPlan` describes how ``ServeEngine`` spreads one model over
a ``(data, tensor)`` device mesh; :class:`ShardedSteps` compiles the
engine's two paged executables (``_prefill_chunk`` / ``_step_paged``)
as ``jit(shard_map(...))`` over that mesh. Everything above the engine
seam — admission, the radix prefix tree, preemption, deadline
scheduling, NaR quarantine — is untouched: the scheduler still thinks
in host-global logical pages, and only the page *contents* (the KV
head dim) live device-local.

Placement (``tensor`` axis, size ``tp``):

* ``wq``/``wk``/``wv``/``wg``/``w1`` column-sharded on their last dim
  via :func:`repro.dist.sharding.param_spec` — rank r owns query heads
  ``[r*H/tp, (r+1)*H/tp)`` and, because GQA groups are contiguous,
  exactly the matching ``Hkv/tp`` KV heads, so per-rank attention needs
  no head traffic at all. ``WireMatrix`` projections shard the same
  way: the wire *words* array is the pytree leaf, and a
  ``PartitionSpec`` at the WireMatrix node acts as a prefix over it.
* the per-layer paged ``PagePool`` K/V shard their ``Hkv`` dim — each
  rank's pool is ``1/tp`` of the HBM (:func:`shard_pool_bytes`);
  block tables / ``pos`` / ``start`` stay replicated (host-global).
* ``wo``/``w2`` are replicated in ``"gather"`` mode (bit-exact parity)
  or row-sharded in ``"psum"`` mode; embeddings and the unembed stay
  replicated (a sharded vocab would silently clamp embed lookups).

Cross-device traffic goes through ``dist.collectives`` ring primitives
with optional wire compression (:data:`COMPRESS_ENV`, default on when
the plan asks for it): interconnect bytes are n/32 of f32, with
error-feedback residuals carried per call-site in the paged cache
(see ``dist/tp.py``). :func:`step_interconnect_bytes` is the analytic
byte census BENCH reports.

Validated on CPU via ``REPRO_HOST_DEVICES=8`` (see
``serve/shard_selftest.py`` and ``docs/serving.md``).
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.dist import tp as _tp
from repro.dist.sharding import param_spec
from repro.kernels.ops import WireMatrix
from repro.models import model
from repro.models.transformer import layer_plan

__all__ = ["ShardPlan", "ShardedSteps", "make_plan", "COMPRESS_ENV"]

# escape hatch: REPRO_SHARD_COMPRESS=0 forces f32 collectives even when
# the plan asks for compression; any other value names the wire format
COMPRESS_ENV = "REPRO_SHARD_COMPRESS"
_OFF = ("0", "off", "none", "")

# exact leaf names sharded on the tensor axis (everything else —
# embeddings, norms, biases — stays replicated)
_COL_SHARDED = ("wq", "wk", "wv", "wg", "w1")   # last dim (heads / d_ff)
_ROW_SHARDED = ("wo", "w2")                     # nd-2 dim, psum mode only


def make_plan(tp: int = 1, dp: int = 1, *, mode: str = "gather",
              compress: Optional[str] = None, env=None) -> "ShardPlan":
    """Build a plan, honouring the :data:`COMPRESS_ENV` escape hatch:
    unset -> the caller's ``compress``; ``0``/``off``/``none`` -> no
    compression; any other value -> that wire format name."""
    env = os.environ if env is None else env
    raw = env.get(COMPRESS_ENV)
    if raw is not None:
        compress = None if raw.strip().lower() in _OFF else raw.strip()
    return ShardPlan(tp=tp, dp=dp, mode=mode, compress=compress)


@dataclasses.dataclass(frozen=True)
class ShardPlan:
    """How to spread one served model over a ``(data, tensor)`` mesh."""
    tp: int = 1                    # tensor-parallel ranks (KV-head shards)
    dp: int = 1                    # data-parallel replicas (logit rows)
    mode: str = "gather"           # "gather" (bit-exact) | "psum"
    compress: Optional[str] = None  # wire format for collectives, or None
    tensor_axis: str = "tensor"
    data_axis: str = "data"

    def __post_init__(self):
        if self.mode not in ("gather", "psum"):
            raise ValueError(f"ShardPlan.mode {self.mode!r}: expected "
                             "'gather' or 'psum'")
        if self.tp < 1 or self.dp < 1:
            raise ValueError(f"ShardPlan tp={self.tp} dp={self.dp}: both "
                             "must be >= 1")
        if self.compress is not None:
            self.wire_spec()  # reject typos before any compile

    @property
    def size(self) -> int:
        return self.tp * self.dp

    def wire_spec(self):
        """The registry ``FormatSpec`` the collectives compress with
        (None = uncompressed f32 wire)."""
        if self.compress is None:
            return None
        from repro import formats
        return formats.resolve_wire(self.compress)

    def validate(self, cfg) -> None:
        """Reject configs the mesh cannot split evenly, by name."""
        for field, val in (("n_heads", cfg.n_heads),
                           ("n_kv_heads", cfg.n_kv_heads),
                           ("d_ff", cfg.d_ff)):
            if val % self.tp:
                raise ValueError(
                    f"ShardPlan(tp={self.tp}) cannot split {field}={val} "
                    f"of {cfg.name!r}: {val} % {self.tp} != 0")

    def build_mesh(self) -> Mesh:
        devs = jax.devices()
        if len(devs) < self.size:
            raise ValueError(
                f"ShardPlan needs {self.size} devices (dp={self.dp} x "
                f"tp={self.tp}) but jax sees {len(devs)}; on CPU set "
                f"REPRO_HOST_DEVICES={self.size} (or XLA_FLAGS="
                f"--xla_force_host_platform_device_count={self.size}) "
                "before importing jax")
        grid = np.array(devs[:self.size]).reshape(self.dp, self.tp)
        return Mesh(grid, (self.data_axis, self.tensor_axis))

    def local_cfg(self, cfg):
        """The per-rank view of ``cfg``: each rank runs ``H/tp`` query
        heads and ``Hkv/tp`` KV heads (GQA groups stay contiguous)."""
        if self.tp == 1:
            return cfg
        return dataclasses.replace(cfg, n_heads=cfg.n_heads // self.tp,
                                   n_kv_heads=cfg.n_kv_heads // self.tp)

    def context(self) -> _tp.TPContext:
        return _tp.TPContext(axis=self.tensor_axis, size=self.tp,
                             mode=self.mode, spec=self.wire_spec(),
                             dp_axis=self.data_axis, dp=self.dp)

    # -- placement rules ---------------------------------------------------

    def leaf_spec(self, name: str, shape) -> P:
        """PartitionSpec for one parameter leaf (``name`` is the
        '/'-joined pytree path; the last segment picks the rule).

        Delegates the dim choice to ``dist.sharding.param_spec`` — the
        same rules the training dry-run uses — but only for the exact
        projection leaves serving shards; everything else is replicated.
        """
        leaf = name.rsplit("/", 1)[-1]
        if self.tp == 1:
            return P()
        if leaf in _ROW_SHARDED and self.mode == "gather":
            return P()  # replicated: every rank matmuls the gathered acts
        if leaf not in _COL_SHARDED + _ROW_SHARDED:
            return P()  # embeddings / norms / biases stay replicated
        return param_spec(name, shape,
                          rules={"ff": (self.tensor_axis,), "batch": None},
                          axis_sizes={self.tensor_axis: self.tp})

    # -- byte accounting ---------------------------------------------------

    def shard_pool_bytes(self, pool) -> int:
        """Per-device HBM of the paged pool: the KV head dim is sharded,
        so each rank holds ``1/tp`` of ``pool.hbm_bytes()``."""
        return pool.hbm_bytes() // self.tp

    def step_interconnect_bytes(self, cfg, batch: int) -> int:
        """Analytic bytes moved across the mesh per decode step (sum
        over all links), from the ring collectives' hop counts — what
        BENCH's ``serving_sharded`` rows report.

        gather mode: each rank's activation chunk travels ``tp - 1``
        hops per seam; psum mode: reduce-scatter + all-gather of the
        ``d_model`` partials (``2 (tp-1) G`` total). The DP logit
        gather adds ``(dp-1) * batch * vocab_padded`` elements. Every
        element is ``wire_spec().bytes_per_elem(f32)`` wide (4 when
        uncompressed).
        """
        spec = self.wire_spec()
        per = 4.0 if spec is None else spec.bytes_per_elem(jnp.float32)
        n_layers = sum(len(pat) * n_rep for pat, n_rep in layer_plan(cfg))
        elems = 0
        if self.tp > 1:
            if self.mode == "gather":
                cols = cfg.n_heads * cfg.hd + cfg.d_ff
                elems += n_layers * (self.tp - 1) * batch * cols
            else:
                elems += n_layers * 2 * 2 * (self.tp - 1) * batch \
                    * cfg.d_model
        if self.dp > 1 and batch % self.dp == 0:
            from repro.models.layers import padded_vocab
            elems += (self.dp - 1) * batch * padded_vocab(cfg.vocab)
        return int(elems * per)

    def describe(self, cfg=None, batch: Optional[int] = None) -> dict:
        """Plain-dict self-description for config audits and traces
        (``launch.env.log_config`` and the obs run metadata embed it).
        With ``cfg``/``batch`` the analytic per-step interconnect bytes
        are included."""
        spec = self.wire_spec()
        out = {"tp": self.tp, "dp": self.dp, "mode": self.mode,
               "compress": None if spec is None else spec.name,
               "devices": self.size}
        if cfg is not None and batch is not None:
            out["interconnect_bytes_per_step"] = \
                self.step_interconnect_bytes(cfg, batch)
        return out


# -- pytree -> PartitionSpec trees ------------------------------------------


def _is_param_leaf(x) -> bool:
    return isinstance(x, WireMatrix)


def _path_name(path) -> str:
    parts = []
    for k in path:
        parts.append(str(getattr(k, "key", getattr(k, "idx", k))))
    return "/".join(parts)


def _param_specs(params, plan: ShardPlan):
    """Tree of PartitionSpecs matching ``params`` with WireMatrix nodes
    as leaves (the spec is a pytree prefix over the words leaf)."""
    return jax.tree_util.tree_map_with_path(
        lambda path, p: plan.leaf_spec(_path_name(path), p.shape),
        params, is_leaf=_is_param_leaf)


def _cache_spec_for(key: str, ndim: int, plan: ShardPlan) -> P:
    if key.startswith("tp_res"):
        # rank-major error-feedback residual: [n_rep, tp, W, 1, C]
        return P(None, plan.tensor_axis)
    if key in ("k", "v") and ndim == 5:
        # paged pool [n_rep, P, Hkv, ps, hd] or contiguous
        # [n_rep, B, Hkv, T, hd]: the KV head dim shards either way
        return P(None, None, plan.tensor_axis, None, None)
    return P()  # table / pos / start: host-global, replicated


def _cache_specs(cache, plan: ShardPlan):
    def spec(path, leaf):
        key = str(getattr(path[-1], "key", "")) if path else ""
        return _cache_spec_for(key, jnp.ndim(leaf), plan)
    return jax.tree_util.tree_map_with_path(spec, cache)


def place_params(params, plan: ShardPlan, mesh: Mesh):
    """``device_put`` every parameter onto the mesh per the plan (the
    explicit placement also feeds jit's ``in_shardings`` inference, so
    the step never re-shards weights per dispatch)."""
    specs = _param_specs(params, plan)
    return jax.tree_util.tree_map(
        lambda p, s: jax.device_put(p, NamedSharding(mesh, s)),
        params, specs, is_leaf=_is_param_leaf)


# -- sharded step functions -------------------------------------------------


class ShardedSteps:
    """Drop-in ``_prefill_chunk`` / ``_step_paged`` / ``_sample_rows``
    built as ``jit(shard_map(...))`` over the plan's mesh. One compiled
    executable per cache tree structure (the paged cache's structure is
    stable; contiguous prefill caches vary by width, matching the
    engine's one-compile-per-width behaviour)."""

    def __init__(self, plan: ShardPlan, cfg, mesh: Optional[Mesh] = None):
        plan.validate(cfg)
        self.plan = plan
        self.cfg = cfg
        self.mesh = plan.build_mesh() if mesh is None else mesh
        self._pspecs = None     # filled on first call (needs params)
        self._fns = {}

    # residual injection ----------------------------------------------------

    def _residual_shapes(self, width: int):
        cfg, plan = self.cfg, self.plan
        if plan.mode == "gather":
            co = cfg.n_heads * cfg.hd // plan.tp
            cm = cfg.d_ff // plan.tp
        else:
            co = cm = cfg.d_model
        return {"tp_res_o": (plan.tp, width, 1, co),
                "tp_res_m": (plan.tp, width, 1, cm)}

    def ensure_residuals(self, cache) -> None:
        """Inject zero error-feedback leaves into every paged attention
        node (in place, idempotent). Only when compressing — exact
        collectives need no feedback, and the extra leaves would change
        the cache treedef the engine's other executables see."""
        if self.plan.wire_spec() is None or self.plan.tp == 1:
            return
        nodes = [group[bname]["attn"] for group in cache
                 for bname in sorted(group)
                 if isinstance(group[bname], dict)
                 and "attn" in group[bname]]
        if not nodes or "tp_res_o" in nodes[0]:
            return
        width = nodes[0]["table"].shape[1]
        shapes = self._residual_shapes(width)
        for node in nodes:
            n_rep = node["table"].shape[0]
            for key, shp in shapes.items():
                node[key] = jnp.zeros((n_rep,) + shp, jnp.float32)

    # step builders ---------------------------------------------------------

    def _ctx(self):
        return self.plan.context()

    def _specs_for(self, params, cache):
        if self._pspecs is None:
            self._pspecs = _param_specs(params, self.plan)
        return self._pspecs, _cache_specs(cache, self.plan)

    def _get(self, kind: str, params, cache, build):
        key = (kind, jax.tree_util.tree_structure(cache))
        fn = self._fns.get(key)
        if fn is None:
            fn = self._fns[key] = build(*self._specs_for(params, cache))
        return fn

    def prefill_chunk(self, params, tokens, cache, pos, last_idx):
        def build(pspecs, cspecs):
            cfg, ctx = self.plan.local_cfg(self.cfg), self._ctx()

            def local(params, tokens, cache, pos, last_idx):
                with _tp.active(ctx):
                    return model.prefill_chunk(params, tokens, cfg, cache,
                                               pos=pos, last_idx=last_idx)

            return jax.jit(jax.shard_map(
                local, mesh=self.mesh,
                in_specs=(pspecs, P(), cspecs, P(), P()),
                out_specs=(P(), cspecs), check_vma=False))
        fn = self._get("prefill", params, cache, build)
        return fn(params, tokens, cache, pos, last_idx)

    def step_paged(self, params, tok, cache, pos, keys, temps, top_ps):
        self.ensure_residuals(cache)

        def build(pspecs, cspecs):
            from repro.serve.engine import sample_rows
            cfg, ctx = self.plan.local_cfg(self.cfg), self._ctx()

            def local(params, tok, cache, pos, keys, temps, top_ps):
                with _tp.active(ctx):
                    logits, cache = model.decode_step(params, tok, cfg,
                                                      cache, pos=pos)
                    toks, new_keys = sample_rows(logits, keys, temps,
                                                 top_ps)
                    bad = jnp.any(jnp.isnan(logits), axis=-1)
                    return toks[:, None], cache, new_keys, bad

            return jax.jit(jax.shard_map(
                local, mesh=self.mesh,
                in_specs=(pspecs, P(), cspecs, P(), P(), P(), P()),
                out_specs=(P(), cspecs, P(), P()), check_vma=False))
        fn = self._get("step", params, cache, build)
        return fn(params, tok, cache, pos, keys, temps, top_ps)

    def sample_rows(self, logits, keys, temps, top_ps):
        from repro.serve.engine import sample_rows
        return jax.jit(sample_rows)(logits, keys, temps, top_ps)
