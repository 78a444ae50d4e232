"""Batched serving engine: continuous batching over the paged KV pool,
with the lockstep bucketed-prefill + jitted-decode loop retained.

Two serving modes share the compiled prefill/step executables:

* **Continuous batching** (``submit``/``run``, and ``generate`` when it
  applies): requests stream through a fixed-width decode batch over the
  paged takum-wire KV pool (``repro.serve.paged`` /
  ``repro.serve.scheduler``) — admission whenever pages free up,
  per-request prefill interleaved with decode, pages released the step
  a sequence finishes. This is where ``cfg.kv_quant`` compression
  becomes *capacity*: takum8 pages fit 4x the concurrent sequences of
  an f32 cache in the same HBM.
* **Lockstep** (``generate_lockstep``): one left-padded batch decodes
  until the slowest sequence finishes — the static-shape baseline the
  scheduler is measured against, and the fallback for everything the
  paged path does not cover (recurrent/encdec families, temperature
  sampling, media prompts).

Supports greedy and temperature sampling, per-sequence stop conditions,
takum-quantised KV caches (``cfg.kv_quant``) and takum weight-only
quantisation (``quantize_weights``).
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Iterator, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, parse_kv_quant
from repro.models import model

__all__ = ["ServeEngine", "quantize_weights", "sample_rows", "CACHE_SLACK"]

# Lockstep cache headroom beyond ``prompt + max_new`` positions: the
# pipelined decode loop launches one step beyond the EOS break (its
# append lands at position ``plen + max_new - 1`` plus the speculative
# step), and recurrent families round the prompt up before the cache is
# sized. 8 covers both without a measurable HBM cost.
CACHE_SLACK = 8


def sample_rows(logits, keys, temps, top_ps):
    """Per-request sampling for the continuous batch: one token per row.

    ``logits [W, V]``, ``keys [W, 2]`` (one PRNG key per row),
    ``temps``/``top_ps [W]`` -> ``(tokens [W] int32, new_keys [W, 2])``.

    Each row follows the per-request key schedule the fuzz tests replay
    by hand: ``key, sub = split(key); token = categorical(sub, logits /
    temp)``. Greedy rows (``temp == 0``) take the argmax — their split
    result is computed under vmap but discarded by the caller, so a
    greedy request consumes no randomness. ``top_p >= 1`` selects the
    *unmasked* scaled logits, making the nucleus filter bit-exactly
    absent rather than a no-op rewrite of the same distribution; below
    1, tokens are sorted by probability and a token is kept while the
    probability mass strictly *before* it is under ``top_p`` (the
    exclusive cumsum always keeps the top token).
    """
    def one(lg, key, temp, top_p):
        key, sub = jax.random.split(key)
        greedy = jnp.argmax(lg).astype(jnp.int32)
        scaled = lg / jnp.maximum(temp, 1e-6)
        probs = jax.nn.softmax(scaled)
        order = jnp.argsort(-probs)
        mass_before = jnp.cumsum(probs[order]) - probs[order]
        keep = jnp.zeros_like(mass_before, bool).at[order].set(
            mass_before < top_p)
        nucleus = jnp.where(keep, scaled, -jnp.inf)
        dist = jnp.where(top_p >= 1.0, scaled, nucleus)
        sampled = jax.random.categorical(sub, dist).astype(jnp.int32)
        return jnp.where(temp > 0.0, sampled, greedy), key

    return jax.vmap(one)(logits, keys, temps, top_ps)


# MoE routing (router weights and selection bias) stays float32
_DEFAULT_SKIP = ("embed", "unembed", "scale", "norm", "router")

# per-format largest finite magnitude, cached by name: decode every word
# of the format once and mask the non-finite (NaR) entry. The identity
# codec has no finite cap (and 2^32 words), so it reports inf.
_FMT_MAX: dict = {}


def _format_max(spec) -> float:
    if spec.is_identity:
        return float("inf")
    if spec.name not in _FMT_MAX:
        # int32 ramp then cast: a uint16 arange over 2^16 words would
        # wrap before the cast for 16-bit formats
        words = jnp.arange(2 ** spec.n, dtype=jnp.int32) \
                   .astype(spec.word_dtype)
        vals = spec.decode_tile(words, jnp.float32)
        finite = jnp.where(jnp.isfinite(vals), jnp.abs(vals), 0.0)
        _FMT_MAX[spec.name] = float(jnp.max(finite))
    return _FMT_MAX[spec.name]


def quantize_weights(params, fmt: str = "takum8", *,
                     mode: str = "fake",
                     skip_substrings=_DEFAULT_SKIP,
                     verbose: bool = True):
    """Quantise a served model's weight matrices to a wire format.

    ``fmt`` is any wire format of the codec registry
    (``repro.formats.wire_names()``): ``"takum8"``/``"takum16"`` are the
    *linear* takum formats; ``"lns-takum8"``/``"lns-takum16"`` the
    *logarithmic* ones — wire leaves then route every ``x @ w`` through
    the ℓ̄-datapath kernel (``ops.lns_matmul``), which also quantises the
    incoming activations to the LNS grid (the LNS-DNN design point), and
    fake-quantised leaves round-trip through the LNS grid unscaled
    (takum's sqrt(e)^±255 range needs no scale side-channel);
    ``"posit8"``/``"posit16"`` are the posit (es = 2, 2C dataflow)
    comparison baseline, riding the same decode-once matmul as linear
    takum — the only posit-specific code is its ``FormatSpec`` entry.

    ``mode="fake"`` (default): quantise-dequantise in place; the model
    runs unchanged on float weights rounded to the takum grid — what
    serving accuracy evaluations use.

    ``mode="wire"``: replace dense projections by a
    :class:`repro.kernels.ops.WireMatrix` holding the raw takum words.
    HBM weight bytes drop to n/32 of f32, and every ``x @ w`` site routes
    through the weight-stationary decode-once matmul kernel (fused XLA
    decode+dot off-TPU) via jax's operator deferral — no model-code
    changes. Layer-stacked (L, din, dout) projections are wired too:
    ``lax.scan`` slices the registered pytree's word leaf per layer, so
    each block sees a 2D WireMatrix. Wire weights are unscaled (takum's
    sqrt(e)^±255 range needs no scale side-channel). Only leaves on the
    ``wire_leaves`` allowlist below are wired — every name on it is
    consumed via a plain ``x @ w`` across all model families (attention
    and MLP projections, rwkv mixer/gate matrices) — and the MoE expert
    stacks ``experts_{wg,w1,w2}`` (``[E, K, N]``, or ``[L, E, K, N]``
    layer-stacked), which become :class:`repro.kernels.ops.ExpertWire`
    words for the grouped expert matmul (float formats only: the LNS
    formats fake-quantise them); anything else — lora factors, skipped
    names, unknown new projections — falls back to in-place fake-quant,
    trading the wire saving for guaranteed compatibility.

    Auditability: one summary line (``n wired / n fake-quantised / n
    skipped``) is printed unless ``verbose=False``; a
    ``skip_substrings`` entry that matches no parameter name raises a
    ``UserWarning`` (typo detection), and a wire-allowlist leaf whose
    ``ndim > 3`` raises instead of silently fake-quantising.
    """
    import warnings

    from repro import formats
    from repro.kernels import ops as kops
    if mode not in ("fake", "wire"):
        raise ValueError(f"unknown quantize_weights mode {mode!r}")
    try:  # one format registry for weights and KV caches (repro.formats)
        spec = formats.resolve_wire(fmt)
    except ValueError:
        # enumerate the registry so this message cannot rot as formats land
        raise ValueError(
            f"unknown quantize_weights fmt {fmt!r} (expected a wire "
            f"format: {', '.join(formats.wire_names())})") from None
    # exact leaf names applied via `x @ w` (matmul defers to WireMatrix);
    # other matrices go through einsum sites that need real arrays
    wire_leaves = {"wq", "wk", "wv", "wo", "wg", "wr", "w1", "w2"}
    expert_leaves = {"experts_wg", "experts_w1", "experts_w2"}
    counts = {"wired": 0, "fake": 0, "skipped": 0, "non_matrix": 0}
    matched: set = set()
    # numeric-health telemetry (REPRO_OBS only): count weights whose
    # magnitude exceeds the format's unscaled finite range — the
    # population fake-quant clamps to the grid edge (linear takum's
    # per-tensor centring usually rescues them; the counter says how
    # often the format is living at its range limit regardless)
    from repro import obs as obsmod
    from repro.obs.metrics import GLOBAL as _metrics
    sat_on = obsmod.enabled()

    def visit(path, leaf):
        parts = [str(getattr(p, "key", p)).strip("'[]") for p in path]
        name = "/".join(parts)
        hits = {s for s in skip_substrings if s in name}
        matched.update(hits)
        if hits:
            counts["skipped"] += 1
            return leaf
        if leaf.ndim < 2:  # never a candidate — kept out of the skip
            counts["non_matrix"] += 1  # count so the audit stays crisp
            return leaf
        named = parts and parts[-1] in wire_leaves \
            and jnp.issubdtype(leaf.dtype, jnp.floating)
        if sat_on and jnp.issubdtype(leaf.dtype, jnp.floating):
            fmax = _format_max(spec)
            if fmax < float("inf"):
                _metrics.counter("quant.saturated").inc(
                    int(jnp.sum(jnp.abs(leaf) > fmax)))
                _metrics.counter("quant.elems").inc(int(leaf.size))
        if mode == "wire" and named and leaf.ndim > 3:
            raise ValueError(
                f"quantize_weights(mode='wire'): {name!r} is on the wire "
                f"allowlist but has ndim={leaf.ndim} > 3 — it would fall "
                "back to fake-quant silently; reshape it or add it to "
                "skip_substrings explicitly")
        if mode == "wire" and named and leaf.ndim in (2, 3):
            counts["wired"] += 1
            return kops.WireMatrix.encode(leaf, fmt=spec)
        if (mode == "wire" and parts[-1] in expert_leaves
                and leaf.ndim in (3, 4) and not spec.has_lns_parts):
            counts["wired"] += 1
            return kops.ExpertWire.encode(leaf, fmt=spec)
        counts["fake"] += 1
        # the spec's fake-quant policy: per-tensor power-of-two centring
        # for linear takum, unscaled grid round trip for LNS/posit
        # (their dynamic range needs no scale side-channel)
        return spec.fake_quant(leaf.astype(jnp.float32),
                               dtype=leaf.dtype)

    out = jax.tree_util.tree_map_with_path(visit, params)
    # only user-supplied entries are typo-checked: the defaults are
    # legitimately absent on some families (tied models have no
    # 'unembed' leaf)
    unmatched = [s for s in skip_substrings
                 if s not in matched and s not in _DEFAULT_SKIP]
    if unmatched:
        warnings.warn(f"quantize_weights: skip_substrings {unmatched} "
                      "matched no parameter name — typo?", stacklevel=2)
    if verbose:
        print(f"quantize_weights[{spec.name}/{mode}]: {counts['wired']} wired, "
              f"{counts['fake']} fake-quantised, {counts['skipped']} "
              f"skipped, {counts['non_matrix']} non-matrix")
    return out


@dataclasses.dataclass
class ServeEngine:
    params: object
    cfg: ModelConfig
    max_len: int              # per-sequence KV position cap (paged mode)
    temperature: float = 0.0
    eos_id: int = -1          # -1: never stop early
    seed: int = 0
    kv_block: Optional[int] = None  # fused-attention KV tile override
    # continuous-batching knobs (submit/run and scheduler-routed generate)
    page_size: Optional[int] = None   # None -> kv_block or the kernel tile
    num_pages: Optional[int] = None   # None -> decode_batch full sequences
    decode_batch: int = 8             # packed decode width (slots)
    prefix_cache: bool = True         # radix-tree shared prompt pages
    preempt: bool = True              # preempt low priority under pressure
    now_fn: Optional[Callable[[], float]] = None  # scheduler clock
                                      # (deadlines/watchdog; None = wall)
    shard: Optional[object] = None    # serve.shard.ShardPlan (None = 1 dev)

    def __post_init__(self):
        parse_kv_quant(self.cfg.kv_quant)  # reject typos before compiling
        if self.kv_block:
            self.cfg = dataclasses.replace(self.cfg, kv_block=self.kv_block)
        self._sched = None
        self._sched_key = None
        cfg = self.cfg

        def _prefill(params, tokens, cache, media):
            return model.prefill(params, tokens, cfg, cache, media=media)

        def _step(params, tok, cache, pos, key, temp):
            logits, cache = model.decode_step(params, tok, cfg, cache,
                                              pos=pos)
            if self.temperature > 0.0:
                nxt = jax.random.categorical(key, logits / temp, axis=-1)
            else:
                nxt = jnp.argmax(logits, axis=-1)
            return nxt.astype(jnp.int32)[:, None], cache

        def _prefill_chunk(params, tokens, cache, pos, last_idx):
            return model.prefill_chunk(params, tokens, cfg, cache, pos=pos,
                                       last_idx=last_idx)

        def _step_paged(params, tok, cache, pos, keys, temps, top_ps):
            logits, cache = model.decode_step(params, tok, cfg, cache,
                                              pos=pos)
            toks, new_keys = sample_rows(logits, keys, temps, top_ps)
            # per-row NaN flag: a corrupted (NaR) wire page read by this
            # row's attention poisons its logits — the scheduler maps
            # the flag back to the owning request and quarantines it
            bad = jnp.any(jnp.isnan(logits), axis=-1)
            return toks[:, None], cache, new_keys, bad

        self._prefill = jax.jit(_prefill)
        self._step = jax.jit(_step)
        # continuous-batching executables: chunked prefill at a traced
        # offset (one compile per contiguous-cache width, not per
        # offset) and the packed decode step with per-slot sampling
        # state — the lockstep _step above keeps the engine-global key
        # schedule the PR 3 parity pins rely on
        self._prefill_chunk = jax.jit(_prefill_chunk)
        self._step_paged = jax.jit(_step_paged)
        self._sample_rows = jax.jit(sample_rows)
        if self.shard is not None and getattr(self.shard, "size", 1) > 1:
            # multi-device plan: place the weights once, then swap the
            # paged executables for the jit(shard_map) versions —
            # everything above this seam (scheduler, prefix tree,
            # preemption, quarantine) is untouched
            from repro.serve import shard as shardmod
            self.shard.validate(cfg)
            mesh = self.shard.build_mesh()
            self.params = shardmod.place_params(self.params, self.shard,
                                                mesh)
            steps = shardmod.ShardedSteps(self.shard, cfg, mesh=mesh)
            self._sharded_steps = steps
            self._prefill_chunk = steps.prefill_chunk
            self._step_paged = steps.step_paged

    # -- continuous batching (paged KV pool + scheduler) -------------------

    def scheduler(self, *, page_size: Optional[int] = None,
                  num_pages: Optional[int] = None,
                  decode_batch: Optional[int] = None,
                  max_pages: Optional[int] = None):
        """The engine's continuous-batching scheduler (built lazily,
        reused while its sizing matches and requests are pending).

        Defaults: ``page_size`` = the fused kernel's KV tile
        (``kv_block`` or ``DEFAULT_BK``), ``max_pages`` =
        ``ceil(max_len / page_size)`` (the per-sequence cap),
        ``num_pages`` = enough for ``decode_batch`` full-length
        sequences plus the reserved scratch page.
        """
        from repro.kernels.takum_attention import DEFAULT_BK
        from repro.serve.paged import pages_for
        from repro.serve.scheduler import Scheduler
        if (self._sched is not None and page_size is None
                and num_pages is None and decode_batch is None
                and max_pages is None):
            # the no-argument call means "the engine's scheduler", not a
            # resize back to the construction defaults
            return self._sched
        ps = page_size or self.page_size or self.cfg.kv_block or DEFAULT_BK
        db = decode_batch or self.decode_batch
        mp = max_pages or max(pages_for(self.max_len, ps), 1)
        npg = num_pages or self.num_pages or (db * mp + 1)
        key = (ps, mp, npg, db, self.prefix_cache, self.preempt)
        if self._sched is not None:
            if self._sched_key == key:
                return self._sched
            if self._sched.pending():
                raise RuntimeError(
                    "cannot resize the scheduler while requests are "
                    f"pending (current {self._sched_key}, wanted {key})")
        prev = self._sched
        self._sched = Scheduler(self, page_size=ps, max_pages=mp,
                                num_pages=npg, decode_batch=db,
                                prefix_cache=self.prefix_cache,
                                preempt=self.preempt, now_fn=self.now_fn)
        if prev is not None:
            # a resize must not lose finished results or reuse rids
            self._sched.adopt_finished(prev)
            if prev.obs is not None:
                # detach the old bundle's compile watcher — otherwise
                # every resize leaks a live listener into the module
                # registry and steady-state recompile counts double up
                prev.obs.close()
        self._sched_key = key
        return self._sched

    def submit(self, prompt: List[int], max_new: int,
               eos_id: Optional[int] = None, *, priority: int = 0,
               temperature: Optional[float] = None, top_p: float = 1.0,
               seed: Optional[int] = None,
               deadline_ms: Optional[float] = None) -> int:
        """Enqueue one request on the paged scheduler; returns a request
        id for :meth:`run`'s stream events and :meth:`result`. Raises
        ``repro.serve.paged.AdmissionError`` (naming the KV format and
        the page budget) when the request can never fit the pool.

        ``priority``: higher admits first (aged so low priorities are
        never starved; under page pressure a strictly-higher priority
        may preempt a running lower one — ``ServeEngine.preempt``).
        ``temperature``/``top_p``: per-request sampling
        (``temperature=None`` inherits the engine's; 0 = greedy).
        ``seed``: per-request PRNG seed (``None`` derives a key from the
        engine seed and the request id, so resubmitting the same prompt
        still draws fresh tokens). ``deadline_ms``: total-latency bound
        on the scheduler clock — a request past it is failed with a
        terminal ``StreamEvent(status="timeout")``."""
        return self.scheduler().submit(
            prompt, max_new, eos_id=eos_id, priority=priority,
            temperature=temperature, top_p=top_p, seed=seed,
            deadline_ms=deadline_ms)

    def cancel(self, rid: int) -> bool:
        """Cancel an in-flight request (pages released, terminal
        ``status="cancelled"`` event emitted); False if it already
        terminated."""
        return self.scheduler().cancel(rid)

    def status(self, rid: int) -> str:
        """The request's lifecycle state (``queued``/``prefilling``/
        ``active`` or a terminal status)."""
        return self.scheduler().status(rid)

    def run(self) -> Iterator["StreamEvent"]:  # noqa: F821 (docs name)
        """Serve every submitted request to completion, streaming
        ``StreamEvent(rid, token, done)`` per generated token."""
        yield from self.scheduler().run()

    def result(self, rid: int) -> List[int]:
        """Finished request's prompt + generated tokens (retained until
        :meth:`forget`)."""
        return self.scheduler().result(rid)

    def forget(self, rid: int) -> None:
        """Drop a finished request's record — long-lived serving loops
        call this after reading the result so host memory stays
        bounded."""
        self.scheduler().forget(rid)

    def timing(self, rid: int):
        """Derived latency stats for a request
        (:class:`repro.obs.trace.RequestTiming` — queue/TTFT/TBT/total
        ms on the scheduler clock). Always available; ``REPRO_OBS``
        gates the span trace, not these host stamps."""
        return self.scheduler().timing(rid)

    @property
    def obs(self):
        """The scheduler's observability bundle
        (:class:`repro.obs.ServeObs`), or ``None`` when no scheduler has
        been built yet or ``REPRO_OBS`` is off."""
        return None if self._sched is None else self._sched.obs

    def trace_records(self, meta: Optional[dict] = None) -> List[dict]:
        """The serving trace as JSONL-shaped records (see
        ``repro.obs.export``). Requires ``REPRO_OBS>=1``."""
        return self.scheduler().trace_records(meta)

    def _can_schedule(self, media) -> bool:
        """Whether ``generate`` can route through the paged scheduler:
        attention-only layer plan, greedy decoding (continuous-batch
        sampling order is schedule-dependent — the lockstep key
        schedule is the pinned behaviour at temperature > 0), and no
        media prompt."""
        from repro.models.transformer import paged_supported
        return (media is None and self.temperature == 0.0
                and paged_supported(self.cfg))

    def generate(self, prompts: List[List[int]], max_new: int,
                 media: Optional[np.ndarray] = None) -> List[List[int]]:
        """Generate ``max_new`` tokens per prompt (prompt + generation
        returned, lockstep-compatible shapes and stop conditions).

        Routed through the continuous-batching scheduler whenever it
        applies (:meth:`_can_schedule`): requests are submitted
        individually and served through the paged takum-wire KV pool —
        admission as pages free up, per-request page-aligned prefill, no
        cross-request padding, pages released at EOS. Falls back to
        :meth:`generate_lockstep` (the original static-batch loop) for
        recurrent/encdec families, temperature sampling, and media
        prompts.
        """
        if not self._can_schedule(media):
            return self.generate_lockstep(prompts, max_new, media=media)
        if self._sched is not None and self._sched.pending():
            # submit()ed requests are in flight: draining them here
            # would consume the stream their owner reads from run()
            # (or force a refused resize) — serve this call lockstep
            return self.generate_lockstep(prompts, max_new, media=media)
        from repro.kernels.takum_attention import DEFAULT_BK
        from repro.serve.paged import pages_for
        # pool sizing is derived from engine fields, not *this call's*
        # prompts: prompts sit at absolute positions [0, plen) whatever
        # the pool shape, so a batched call and its solo replay quantise
        # identical wire words — but a per-call pool would churn
        # compiles. The page size is clamped to the engine's
        # per-sequence cap so toy max_len engines compile small pools,
        # and the table is wide enough for a full-length prompt plus
        # this call's growth.
        ps = self.page_size or self.cfg.kv_block or DEFAULT_BK
        ps = min(ps, max(8, -(-self.max_len // 8) * 8))
        bucket_max = max(-(-len(p) // ps) * ps for p in prompts)
        cap = max(-(-self.max_len // ps) * ps, bucket_max) + max_new - 1
        mp = pages_for(cap, ps)
        sched = self.scheduler(page_size=ps, max_pages=mp,
                               num_pages=self.num_pages
                               or (self.decode_batch * mp + 1))
        rids = [sched.submit(p, max_new) for p in prompts]
        for _ in sched.run():
            pass
        outs = [sched.result(r) for r in rids]
        for r in rids:                  # keep host memory bounded
            sched.forget(r)
        return outs

    # -- lockstep (static batch) -------------------------------------------

    def generate_lockstep(self, prompts: List[List[int]], max_new: int,
                          media: Optional[np.ndarray] = None
                          ) -> List[List[int]]:
        """The static-batch loop: prompts left-padded to one length,
        decode until every sequence finishes. Baseline for the
        scheduler's parity pins and the path for families/sampling the
        paged pool does not cover."""
        cfg = self.cfg
        b = len(prompts)
        plen = max(len(p) for p in prompts)
        if cfg.family == "rwkv6":
            plen = -(-plen // 64) * 64  # chunk alignment
        prompt = np.zeros((b, plen), np.int32)
        start = np.zeros((b,), np.int32)
        for i, p in enumerate(prompts):  # left-pad (last token at the end)
            prompt[i, plen - len(p):] = p
            start[i] = plen - len(p)

        # per-sequence start indices mask out the left padding (recurrent
        # families absorb pads into their state: use equal-length prompts
        # for rwkv6/hybrid)
        use_start = cfg.family not in ("rwkv6", "hybrid_rglru") and \
            start.any()
        max_len = plen + max_new + CACHE_SLACK
        from repro.kernels.ops import interpret_default
        from repro.models.layers import KV_ATTN_KERNEL
        if (KV_ATTN_KERNEL if KV_ATTN_KERNEL is not None
                else not interpret_default()):
            # fused-kernel dispatch active (any kv_quant — the float
            # cache rides the kernel too): align the cache to the KV
            # tile, else ops.takum_attention re-pads (copies) the whole
            # cache every decode step. Extra slots sit beyond `pos` and
            # are causally masked. The off-TPU oracle path needs no
            # alignment and keeps the smaller cache.
            from repro.kernels.takum_attention import DEFAULT_BK
            blk = cfg.kv_block or DEFAULT_BK
            max_len = -(-max_len // blk) * blk
        cache = model.init_cache(cfg, batch=b, max_len=max_len,
                                 start=start if use_start else None)
        logits_last, cache = self._prefill(
            self.params, jnp.asarray(prompt), cache,
            None if media is None else jnp.asarray(media))
        key = jax.random.PRNGKey(self.seed)
        if self.temperature > 0.0:
            # sample the first post-prefill token through the same
            # temperature path as _step (it used to be argmax'd
            # unconditionally, making token 0 greedy at any temperature)
            key, sub = jax.random.split(key)
            tok = jax.random.categorical(
                sub, logits_last / max(self.temperature, 1e-6),
                axis=-1).astype(jnp.int32)[:, None]
        else:
            tok = jnp.argmax(logits_last, axis=-1).astype(jnp.int32)[:, None]
        out = [list(p) for p in prompts]
        done = np.zeros(b, bool)
        temp_arr = jnp.asarray(max(self.temperature, 1e-6))
        for s in range(max_new):
            # launch step s+1 *before* reading step s's token back: the
            # host-side append/EOS check runs one step stale, so the
            # device dispatch pipeline never drains on the per-token
            # sync (the break below discards the speculative step;
            # CACHE_SLACK covers its cache append)
            key, sub = jax.random.split(key)
            nxt, cache = self._step(self.params, tok, cache,
                                    jnp.asarray(plen + s), sub, temp_arr)
            tok_host = np.asarray(tok)
            for i in range(b):
                if not done[i]:
                    out[i].append(int(tok_host[i, 0]))
            done |= tok_host[:, 0] == self.eos_id
            if done.all():
                break
            tok = nxt
        return out
