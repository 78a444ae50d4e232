"""Deterministic wire-page fault injection for the paged serving stack.

The codec's entire exception model is **NaR** — one reserved word per
format (``FormatSpec.nar_word``, sign bit alone) that every decode path
pins to NaN, poisoning exactly the rows that read it. That makes bit
corruption in a wire page *detectable and containable per request*: a
corrupted word that decodes to NaR turns the owning request's logits to
NaN at the next step it is read, while every other sequence in the
packed batch — reading its own pages — continues bit-exactly. The
:class:`FaultInjector` exists to exercise that containment story
end-to-end: it corrupts pool pages between scheduler steps (simulating
HBM / interconnect bit errors), and the scheduler's NaN-in-logits
detector maps the damage back to the owning request, fails it with
``status="poisoned"``, and quarantines its pages out of the free list
(``PagePool.quarantine``).

Determinism: the injector owns a ``numpy`` Generator seeded at
construction, so a given (seed, rate, schedule) triple replays the same
faults — the chaos tests and the ``serving_faults`` BENCH rows rely on
it. An *integer* rate injects exactly that many faults per scheduler
tick; a fractional remainder adds one more fault with that probability.

Targets:

* ``"live"`` (default) — corrupt a position an **active sequence has
  already written** (host ``pos``/``table`` mirrors say which), so the
  fault is read — and detected — at the very next decode step. This is
  the mode the deterministic tests and BENCH gates use.
* ``"in_use"`` — any allocated page, any offset. Faults past a
  sequence's ``pos`` are *latent*: the fresh append overwrites them
  before any read, so they never surface (exactly like real corruption
  of not-yet-valid cache words).
* ``"any"`` — any non-scratch page, allocated or free.

Kinds:

* ``"nar"`` (default) — write the format's NaR word (NaN for the
  identity codec): corruption the NaN detector is *guaranteed* to
  catch once read.
* ``"flip"`` — XOR one uniformly random bit of the stored word
  (bit-flipped f32 for the identity codec). A flipped wire word is
  usually a different *value*, not NaR — this models **silent** numeric
  corruption the NaN detector does not promise to catch; only flips
  that happen to produce NaR/NaN are detected.

Env knobs (read by ``Scheduler`` at construction via
:func:`injector_from_env`): ``REPRO_FAULT_RATE`` (faults per scheduler
tick, 0/unset disables), ``REPRO_FAULT_SEED`` (default 0),
``REPRO_FAULT_KIND`` (``nar``/``flip``).
"""

from __future__ import annotations

import dataclasses
import os
from typing import Callable, List, Optional, Tuple

import numpy as np

__all__ = ["FaultInjector", "FaultRecord", "injector_from_env",
           "FAULT_RATE_ENV", "FAULT_SEED_ENV", "FAULT_KIND_ENV"]

FAULT_RATE_ENV = "REPRO_FAULT_RATE"
FAULT_SEED_ENV = "REPRO_FAULT_SEED"
FAULT_KIND_ENV = "REPRO_FAULT_KIND"


@dataclasses.dataclass(frozen=True)
class FaultRecord:
    """One injected fault, host-side ledger entry (``injected``)."""
    tick: int                       # scheduler tick the fault landed on
    slot: int                       # decode slot targeted (-1: page-mode)
    page: int                       # pool page corrupted
    node: int                       # index into the stacked attn nodes
    key: str                        # "k" | "v"
    rep: int                        # scan-replica index within the node
    offset: Tuple[int, int, int]    # (pos-in-page, kv head, element)
    kind: str                       # "nar" | "flip"


class FaultInjector:
    """Seeded bit-corruption of pool pages between scheduler steps.

    ``rate`` is faults per :meth:`step` call (the scheduler calls it
    once per tick); ``max_faults`` caps the total ever injected (the
    chaos tests use it to bound the blast radius deterministically).
    All injected faults are recorded in ``self.injected``;
    ``faulted_pages()`` is the set of pages ever corrupted — the test
    oracle for which requests may legitimately differ from a fault-free
    run.
    """

    def __init__(self, pool, *, rate: float = 1.0, seed: int = 0,
                 kind: str = "nar", target: str = "live",
                 max_faults: Optional[int] = None):
        if kind not in ("nar", "flip"):
            raise ValueError(f"unknown fault kind {kind!r}")
        if target not in ("live", "in_use", "any"):
            raise ValueError(f"unknown fault target {target!r}")
        if rate < 0:
            raise ValueError(f"fault rate must be >= 0, got {rate}")
        self.pool = pool
        self.rate = float(rate)
        self.seed = int(seed)
        self.kind = kind
        self.target = target
        self.max_faults = max_faults
        self._rng = np.random.default_rng(seed)
        self.injected: List[FaultRecord] = []
        # observer(record) fires on every injection — the scheduler
        # wires it to the obs event stream so faults are visible in a
        # trace, not just in this ledger. Never consulted for targeting:
        # observation cannot change the deterministic fault schedule.
        self.observer: Optional[Callable[[FaultRecord], None]] = None

    # -- target selection --------------------------------------------------

    def faulted_pages(self) -> set:
        return {r.page for r in self.injected}

    def _pick_site(self):
        """(slot, page, pos-in-page) or None when no target exists."""
        pool, rng = self.pool, self._rng
        ps = pool.page_size
        if self.target == "live":
            live = [s for s in range(pool.batch) if pool.pos[s] > 0]
            if not live:
                return None
            slot = int(live[rng.integers(len(live))])
            pi = int(rng.integers(int(pool.pos[slot])))
            return slot, int(pool.table[slot, pi // ps]), pi % ps
        if self.target == "in_use":
            pages = sorted(pool._refs)
            if not pages:
                return None
            return -1, int(pages[rng.integers(len(pages))]), \
                int(rng.integers(ps))
        return -1, int(rng.integers(1, pool.num_pages)), \
            int(rng.integers(ps))

    # -- corruption --------------------------------------------------------

    def _corrupt(self, tick: int, slot: int, page: int, pi: int
                 ) -> FaultRecord:
        import jax.numpy as jnp
        pool, rng = self.pool, self._rng
        nodes = list(pool._attn_nodes(pool.cache))
        node = int(rng.integers(len(nodes)))
        key = "k" if rng.integers(2) == 0 else "v"
        arr = nodes[node][key]          # (n_rep, num_pages, Hkv, ps, hd)
        rep = int(rng.integers(arr.shape[0]))
        head = int(rng.integers(arr.shape[2]))
        elem = int(rng.integers(arr.shape[4]))
        idx = (rep, page, head, pi, elem)
        spec = pool.spec
        if self.kind == "nar":
            word = (jnp.nan if spec.is_identity
                    else jnp.asarray(spec.nar_word, arr.dtype))
        else:  # flip one uniformly random stored bit
            old = np.asarray(arr[idx])
            if spec.is_identity:
                bits = old.astype(np.float32).view(np.uint32)
                bits ^= np.uint32(1) << np.uint32(rng.integers(32))
                word = jnp.asarray(bits.view(np.float32), arr.dtype)
            else:
                word = jnp.asarray(
                    int(old) ^ (1 << int(rng.integers(spec.n))), arr.dtype)
        nodes[node][key] = arr.at[idx].set(word)
        rec = FaultRecord(tick=tick, slot=slot, page=page, node=node,
                          key=key, rep=rep, offset=(pi, head, elem),
                          kind=self.kind)
        self.injected.append(rec)
        if self.observer is not None:
            self.observer(rec)
        return rec

    def step(self, tick: int) -> List[FaultRecord]:
        """Inject this tick's faults into the pool's device pages.

        The integer part of ``rate`` lands deterministically; the
        fractional part is one extra Bernoulli fault. Returns the
        records injected this call (also appended to ``injected``)."""
        n = int(self.rate)
        frac = self.rate - n
        if frac > 0 and self._rng.random() < frac:
            n += 1
        out: List[FaultRecord] = []
        for _ in range(n):
            if (self.max_faults is not None
                    and len(self.injected) >= self.max_faults):
                break
            site = self._pick_site()
            if site is None:
                continue
            out.append(self._corrupt(tick, *site))
        return out


def injector_from_env(pool) -> Optional[FaultInjector]:
    """Build an injector from ``REPRO_FAULT_RATE``/``_SEED``/``_KIND``
    (``None`` when the rate is unset or 0 — the production default)."""
    rate = float(os.environ.get(FAULT_RATE_ENV) or 0.0)
    if rate <= 0:
        return None
    return FaultInjector(
        pool, rate=rate,
        seed=int(os.environ.get(FAULT_SEED_ENV) or 0),
        kind=os.environ.get(FAULT_KIND_ENV) or "nar")
