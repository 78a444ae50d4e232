"""Continuous-batching scheduler over the paged takum-wire KV pool.

The lockstep engine batches requests once, left-pads every prompt to the
longest, and decodes until the *last* sequence finishes — finished
sequences burn decode steps and every sequence pays
``max(prompt) + max_new`` cache slots. This scheduler instead treats
serving as a stream:

* **submit** enqueues a request after validating it can ever fit the
  page budget (:class:`repro.serve.paged.AdmissionError` otherwise —
  the format name and budget in the message, not an OOM inside jit),
  with per-request ``priority``, ``temperature``/``top_p`` sampling
  parameters and an optional PRNG ``seed``;
* **admission** is by priority with aging (FIFO within a priority
  band): each loop tick the highest effective priority whose worst-case
  pages fit is admitted — head-of-line blocking is deliberate, it keeps
  big requests from starving behind a stream of small ones, and aging
  (+1 priority every ``AGING_TICKS`` ticks queued) keeps low priorities
  from starving behind high ones;
* **prompts are never padded**: a request's tokens sit at absolute
  positions ``[0, plen)``. That makes every sequence's KV — and with a
  wire-format cache, its encoded words — *batch-invariant*: exactly
  what a batch-of-1 lockstep run produces, whatever else is in flight.
  Batch invariance is also what makes cross-request prefix sharing
  sound (a shared page's post-RoPE words cannot depend on who reads
  them);
* **prefix cache**: a radix tree over the page pool
  (:class:`repro.serve.prefix.PrefixCache`) shares full pages of common
  prompt prefixes across block tables, refcounted, copy-on-write when a
  fully-cached prompt needs its last page recomputed for logits;
* **prefill is chunked**: an admitted request prefills one
  ``page_size`` chunk per loop tick on a private contiguous cache
  (seeded with the shared prefix pages via ``gather_prefix``),
  interleaved with the decode batch so a long prompt never stalls
  decoding; finished prompts are scattered into their pages
  (``scatter_prefill``) — the same seam one-shot prefill used;
* **decode packs** all active sequences into one fixed-width compiled
  step — per-sequence ``pos`` vectors, per-slot sampling state
  (key/temperature/top-p rows; greedy rows consume no randomness), and
  the block table ride into the paged attention kernel; idle slots
  point at the reserved scratch page;
* **release is immediate**: the step a sequence emits EOS or hits
  ``max_new``, its pages are unreferenced — private pages return to the
  free list, tree-donated pages live on under the prefix cache until
  evicted.

Tokens are deterministic per request — greedy requests are pinned
bit-identical to solo lockstep generation, sampled requests to the
per-request key schedule ``key, sub = split(key); tok =
categorical(sub, logits / temp)`` — and *independent of the schedule*:
priorities and page pressure change when a token is produced, never its
value.

**Failure model** (``docs/serving.md`` has the full story): every
request terminates in exactly one state of :data:`TERMINAL` —

* **preemption**: when admission cannot secure a slot or pages, the
  lowest-effective-priority *running* request (strictly below the
  candidate) is preempted — pages unreferenced honoring COW refcounts,
  request requeued at its original priority with its generated tokens
  as a prompt extension (``Request.prefill_tokens``). Re-admission
  rides the normal prefix-cache/chunked-prefill path, and because
  prompts sit at absolute positions with post-RoPE wire words, the
  resumed request's tokens are bit-identical to an uninterrupted run
  (the per-request PRNG key survives on the host record);
* **deadlines / cancellation**: ``submit(deadline_ms=...)`` and
  :meth:`Scheduler.cancel` fail a request mid-flight — pages released,
  slot cleared, a terminal ``StreamEvent(status="timeout"|"cancelled",
  token=-1)`` emitted. Deadlines are checked once per tick against the
  deterministic ``now_fn`` clock (the ``ft.watchdog`` idiom), which
  also drives a scheduler heartbeat into a :class:`ft.watchdog.Watchdog`
  so a stalled step is externally detectable (:meth:`stalled`);
* **NaR quarantine**: corrupted wire pages (``repro.serve.faults``
  injects them deterministically in tests) decode to NaN; the loop
  checks per-row NaN-in-logits, maps the row to its owning request,
  fails it with ``status="poisoned"``, quarantines its pages out of the
  free list (``PagePool.quarantine``) and evicts them from the radix
  tree (``PrefixCache.evict_pages``) — every other request continues
  bit-exactly on its own pages.

Compilation: one decode-step executable per (decode_batch, table-width)
pool shape, one chunk-prefill executable per distinct contiguous-cache
width (prompt pages + one slack page; the chunk length is always
``page_size`` — tails are right-padded with scratch tokens whose cache
writes are causally masked).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, Iterator, List, Optional, Tuple

import numpy as np

from repro.ft.watchdog import Heartbeat, Watchdog
from repro.obs import obs_from_env
from repro.obs.trace import SCHED_TRACK, RequestTiming, phase
from repro.serve.faults import injector_from_env
from repro.serve.paged import AdmissionError, PagePool, pages_for
from repro.serve.prefix import PrefixCache, PrefixPlan

__all__ = ["Scheduler", "Request", "StreamEvent", "RequestFailed",
           "AGING_TICKS", "TERMINAL"]

# a queued request gains one effective priority level per this many
# scheduler ticks: low-priority requests cannot starve forever
AGING_TICKS = 32

# every request ends in exactly one of these states; "done" is the only
# successful one (the rest raise RequestFailed from result())
TERMINAL = ("done", "timeout", "cancelled", "poisoned")


class RequestFailed(RuntimeError):
    """``result()`` of a request that terminated without completing.

    Carries the terminal ``status`` and the tokens generated before the
    failure (``tokens`` — a timed-out request's partial output is often
    still useful to the caller)."""

    def __init__(self, rid: int, status: str, tokens: List[int]):
        super().__init__(
            f"request {rid} terminated with status {status!r} after "
            f"{len(tokens)} generated tokens")
        self.rid = rid
        self.status = status
        self.tokens = tokens


@dataclasses.dataclass
class Request:
    """One submitted generation request and its lifecycle state."""
    rid: int
    prompt: List[int]
    max_new: int
    eos_id: int
    pages_needed: int           # worst-case pages, secured at admission
    priority: int = 0
    temperature: float = 0.0
    top_p: float = 1.0
    seed: Optional[int] = None
    deadline: Optional[float] = None   # absolute now_fn() seconds
    state: str = "queued"       # queued | prefilling | active | TERMINAL
    generated: List[int] = dataclasses.field(default_factory=list)
    slot: int = -1
    pages: Tuple[int, ...] = ()
    submit_tick: int = 0
    # host timestamps on the scheduler clock (always stamped — three
    # float stores per token; the obs span trace is what REPRO_OBS
    # gates). tok_times[i] is when generated[i] was read on the host.
    t_submit: float = 0.0
    t_admit: Optional[float] = None     # first pages secured
    t_first: Optional[float] = None     # first generated token
    t_end: Optional[float] = None       # terminal transition
    tok_times: List[float] = dataclasses.field(default_factory=list)
    _timing: object = None              # terminal RequestTiming snapshot
    # prefill progress (state == "prefilling")
    _contig: object = None      # private contiguous cache
    _cursor: int = 0            # next prompt position to prefill
    _first_page: int = 0        # first contig page scattered back
    _key: object = None         # per-request PRNG key (device, temp > 0)

    @property
    def done(self) -> bool:
        """Terminated — successfully or not (see :data:`TERMINAL`)."""
        return self.state in TERMINAL

    @property
    def prefill_tokens(self) -> List[int]:
        """The token stream prefill must cover: the prompt, extended by
        whatever was already generated. Fresh requests: just the prompt.
        A *preempted* request resumes by prefilling this — absolute
        positions + post-RoPE wire words make the recomputed KV
        bit-identical to what it held before preemption, and the prefix
        tree may serve most of it from the pages it donated earlier."""
        return list(self.prompt) + list(self.generated)

    def output(self) -> List[int]:
        """Prompt + generated tokens (the lockstep ``generate`` shape)."""
        return list(self.prompt) + list(self.generated)


@dataclasses.dataclass(frozen=True)
class StreamEvent:
    """One streamed token: ``done`` marks the request's last event.

    ``status`` is ``"ok"`` on every token event; a request that fails
    emits exactly one terminal event with ``token=-1``, ``done=True``
    and ``status`` in ``("timeout", "cancelled", "poisoned")``.

    ``t`` is the event's timestamp on the scheduler clock (the
    injectable ``now_fn`` — monotonic seconds by default, a test's fake
    clock under test), so TTFT and inter-token gaps are measurable from
    the stream itself. The ``done=True`` event additionally carries the
    request's full derived :class:`~repro.obs.trace.RequestTiming`
    (queue/TTFT/TBT/total — also retrievable later via
    ``Scheduler.timing``). Both fields are stamped unconditionally;
    ``REPRO_OBS`` gates the span trace, not these."""
    rid: int
    token: int
    done: bool
    status: str = "ok"
    t: float = 0.0
    timing: Optional[RequestTiming] = None

    def matches(self, rid: int, token: int, done: bool,
                status: str = "ok") -> bool:
        """Equality on the stream payload, ignoring the timing fields
        (what tests pin: timestamps depend on the clock, tokens must
        not)."""
        return (self.rid, self.token, self.done, self.status) == \
            (rid, token, done, status)


class Scheduler:
    """Continuous batching over a :class:`PagePool` for a ``ServeEngine``.

    Construction is cheap except for the pool's device arrays; the
    engine builds one lazily (``ServeEngine.scheduler()``) and reuses it
    across ``submit``/``run``/``generate`` calls.
    """

    def __init__(self, engine, *, page_size: int, max_pages: int,
                 num_pages: int, decode_batch: int,
                 prefix_cache: bool = True, preempt: bool = True,
                 now_fn: Optional[Callable[[], float]] = None,
                 stall_after: float = 60.0, injector="env"):
        from repro.models import transformer
        from repro.models.layers import ATTN_CHUNK_T
        if not transformer.paged_supported(engine.cfg):
            raise ValueError(
                f"continuous batching needs an attention-only layer plan; "
                f"family {engine.cfg.family!r} has non-attention state "
                "(use the lockstep ServeEngine.generate)")
        if page_size >= ATTN_CHUNK_T:
            # chunk prefill rides the cached-prefill attention branch;
            # at ATTN_CHUNK_T the fresh-prefill fast path would claim a
            # t > 1 call and assume pos == 0
            raise ValueError(f"page_size must be < {ATTN_CHUNK_T}, "
                             f"got {page_size}")
        self.engine = engine
        self.decode_batch = decode_batch
        self.page_size = page_size
        self.pool = PagePool(engine.cfg, batch=decode_batch,
                             num_pages=num_pages, page_size=page_size,
                             max_pages=max_pages)
        self.prefix: Optional[PrefixCache] = \
            PrefixCache(self.pool) if prefix_cache else None
        self._queue: List[Request] = []
        self._requests: Dict[int, Request] = {}
        self._slots: List[Optional[Request]] = [None] * decode_batch
        self._next_rid = 0
        self._tick = 0
        self._plan_gather = None   # _secure_pages -> _start_prefill handoff
        self.prompt_tokens_submitted = 0
        # failure-model state: deterministic clock (tests inject a fake
        # one — the ft.watchdog idiom), a single-host watchdog fed one
        # heartbeat per tick (an external observer calls stalled()), the
        # buffer of terminal failure events awaiting the stream, the
        # preemption policy switch + counter, and the optional fault
        # injector ("env": built from REPRO_FAULT_RATE/_SEED/_KIND,
        # which default to off)
        self._now: Callable[[], float] = now_fn or time.monotonic
        # observability bundle (None when REPRO_OBS=0/unset): span
        # tracer + metrics registry + compile watcher, all on the
        # scheduler clock. Every hook below is a None-check — obs must
        # be token-neutral AND near-free when off.
        self.obs = obs_from_env(self._now)
        # the phases' second sink (repro.obs.phase): None when obs is off
        self._tracer = None if self.obs is None else self.obs.tracer
        self.watchdog = Watchdog(
            1, dead_after=stall_after, now_fn=self._now,
            on_transition=None if self.obs is None else self._obs_host)
        self._pending: List[StreamEvent] = []
        self.preempt = preempt
        self.preemptions = 0
        self.injector = (injector_from_env(self.pool)
                         if injector == "env" else injector)

    # -- queueing ----------------------------------------------------------

    def submit(self, prompt: List[int], max_new: int,
               eos_id: Optional[int] = None, *, priority: int = 0,
               temperature: Optional[float] = None, top_p: float = 1.0,
               seed: Optional[int] = None,
               deadline_ms: Optional[float] = None) -> int:
        """Enqueue a request; returns its request id.

        Raises :class:`AdmissionError` immediately when the request can
        *never* run: its worst-case page count exceeds the pool budget
        or the block-table width (chunked prefill does not change the
        worst case — every prompt page must be resident at once for
        decode). Requests that merely have to wait for pages stay
        queued.

        ``deadline_ms`` bounds the request's *total* latency: measured
        on the scheduler clock from submit, a request (queued or
        in-flight) past its deadline is failed with a terminal
        ``StreamEvent(status="timeout")`` at the next tick and its pages
        released.
        """
        prompt = list(prompt)
        if not prompt:
            raise ValueError("empty prompt")
        if max_new < 1:
            raise ValueError(f"max_new must be >= 1, got {max_new}")
        temperature = (self.engine.temperature if temperature is None
                       else float(temperature))
        if temperature < 0.0:
            raise ValueError(f"temperature must be >= 0, got {temperature}")
        if not 0.0 < top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")
        ps = self.page_size
        # the last KV write lands at plen + max_new - 2 (the final token
        # is sampled, never written), so the worst case spans
        # plen + max_new - 1 positions — no padding, prompts sit at
        # absolute positions [0, plen)
        needed = pages_for(len(prompt) + max_new - 1, ps)
        pool = self.pool
        if needed > pool.max_pages:
            raise AdmissionError(
                f"request needs {needed} pages of {ps} "
                f"({len(prompt)} prompt + {max_new} new tokens) but the "
                f"block table holds {pool.max_pages} pages/sequence "
                f"({pool.max_pages * ps} positions) — raise "
                "ServeEngine.max_len or the page budget")
        if needed > pool.num_pages - 1:
            raise AdmissionError(
                f"request needs {needed} pages of {ps} "
                f"({len(prompt)} prompt + {max_new} new tokens) but the "
                f"{pool.spec.name} pool budget is {pool.num_pages - 1} "
                f"allocatable pages ({pool.hbm_bytes()} HBM bytes) — "
                "raise num_pages or shorten the request")
        rid = self._next_rid
        self._next_rid += 1
        if deadline_ms is not None and deadline_ms <= 0:
            raise ValueError(f"deadline_ms must be > 0, got {deadline_ms}")
        now = self._now()
        req = Request(rid=rid, prompt=prompt, max_new=max_new,
                      eos_id=self.engine.eos_id if eos_id is None else eos_id,
                      pages_needed=needed, priority=priority,
                      temperature=temperature, top_p=top_p, seed=seed,
                      deadline=(None if deadline_ms is None
                                else now + deadline_ms / 1000.0),
                      submit_tick=self._tick, t_submit=now)
        self._requests[rid] = req
        self._queue.append(req)
        self.prompt_tokens_submitted += len(prompt)
        if self.obs is not None:
            tr = self.obs.tracer
            tr.begin(rid, "request", t=now, prompt_tokens=len(prompt),
                     max_new=max_new, priority=priority,
                     pages_needed=needed)
            tr.begin(rid, "queued", t=now)
            self.obs.metrics.counter("sched.requests_submitted").inc()
        return rid

    def result(self, rid: int) -> List[int]:
        """Finished request's prompt + generated tokens. Records are
        retained until :meth:`forget` — long-lived serving loops should
        forget after reading so host memory stays bounded. Raises
        :class:`RequestFailed` (carrying the status and partial tokens)
        for a request that timed out, was cancelled, or was poisoned."""
        if rid not in self._requests:
            raise KeyError(f"unknown or forgotten request id {rid}")
        req = self._requests[rid]
        if not req.done:
            raise ValueError(f"request {rid} is {req.state}, not done")
        if req.state != "done":
            raise RequestFailed(rid, req.state, list(req.generated))
        return req.output()

    def status(self, rid: int) -> str:
        """The request's current state (lifecycle or :data:`TERMINAL`)."""
        if rid not in self._requests:
            raise KeyError(f"unknown or forgotten request id {rid}")
        return self._requests[rid].state

    def timing(self, rid: int) -> RequestTiming:
        """Derived latency stats for a request (queue/TTFT/TBT/total
        milliseconds on the scheduler clock). Terminal requests return
        the frozen terminal snapshot (the same object the ``done=True``
        stream event carried); in-flight requests a live partial view
        (``total_ms`` up to now). Always available — the host stamps
        behind it are unconditional, not ``REPRO_OBS``-gated."""
        if rid not in self._requests:
            raise KeyError(f"unknown or forgotten request id {rid}")
        req = self._requests[rid]
        if req._timing is not None:
            return req._timing
        return RequestTiming.from_stamps(
            req.rid, req.state, t_submit=req.t_submit,
            t_admit=req.t_admit, t_first=req.t_first,
            tok_times=req.tok_times,
            t_end=req.t_end if req.t_end is not None else self._now())

    def cancel(self, rid: int) -> bool:
        """Cancel a request mid-flight: pages released (COW refcounts
        honored), decode slot cleared, a terminal
        ``StreamEvent(status="cancelled")`` emitted at the next stream
        drain. Returns False when the request already terminated (its
        result stands); raises KeyError for unknown/forgotten ids."""
        if rid not in self._requests:
            raise KeyError(f"unknown or forgotten request id {rid}")
        req = self._requests[rid]
        if req.done:
            return False
        self._fail(req, "cancelled")
        return True

    def forget(self, rid: int) -> None:
        """Drop a request's record. An in-flight request is routed
        through the cancel path first — forget can never leak pages or
        strand a decode slot — and its buffered terminal event is
        dropped with the record (nobody is listening for it)."""
        req = self._requests.get(rid)
        if req is None:
            return
        if not req.done:
            self._fail(req, "cancelled")
        self._pending = [e for e in self._pending if e.rid != rid]
        del self._requests[rid]

    def adopt_finished(self, other: "Scheduler") -> None:
        """Carry another (idle) scheduler's finished records and rid
        counter over — a pool resize must not lose retrievable results
        or reuse request ids."""
        self._requests.update(
            {r: q for r, q in other._requests.items() if q.done})
        self._next_rid = max(self._next_rid, other._next_rid)

    def pending(self) -> int:
        """Requests not yet finished (queued or active)."""
        return sum(1 for r in self._requests.values() if not r.done)

    # -- the serving loop --------------------------------------------------

    def run(self) -> Iterator[StreamEvent]:
        """Drive the schedule until queue and batch drain, streaming
        every generated token as a :class:`StreamEvent` (terminal
        failure events included — every submitted request produces
        exactly one ``done=True`` event)."""
        obs = self.obs
        while (self._queue or self._pending
               or any(s is not None for s in self._slots)):
            self._tick += 1
            if obs is not None:
                # (re)wire the injector's observer lazily: tests and the
                # chaos bench install injectors after construction
                inj = self.injector
                if inj is not None and getattr(inj, "observer", 1) is None:
                    inj.observer = self._obs_fault
                obs.tracer.begin(SCHED_TRACK, "tick", tick=self._tick)
            with phase("housekeeping", self._tracer):
                self._heartbeat()
                if self.injector is not None:
                    self.injector.step(self._tick)
                self._check_deadlines()
            yield from self._drain_pending()
            with phase("admit", self._tracer, queued=len(self._queue)):
                self._admit()
            yield from self._prefill_tick()
            yield from self._decode_step()
            yield from self._drain_pending()
            if obs is not None:
                self._obs_sample()
                obs.tracer.end(SCHED_TRACK, "tick")

    def _drain_pending(self) -> Iterator[StreamEvent]:
        events, self._pending = self._pending, []
        yield from events

    # -- observability hooks (every call site is None-guarded) -------------

    def _obs_host(self, host: int, state: str) -> None:
        """Watchdog health transition -> scheduler-track instant."""
        self.obs.tracer.instant(SCHED_TRACK, f"watchdog_{state}",
                                host=host)
        self.obs.metrics.counter(f"watchdog.{state}").inc()

    def _obs_fault(self, rec) -> None:
        """FaultRecord -> instant on the owning request's track (the
        slot's occupant at injection time; scheduler track otherwise)."""
        req = (self._slots[rec.slot]
               if 0 <= rec.slot < len(self._slots) else None)
        track = req.rid if req is not None else SCHED_TRACK
        self.obs.tracer.instant(track, "fault", tick=rec.tick,
                                page=rec.page, kind=rec.kind,
                                key=rec.key, slot=rec.slot)
        self.obs.metrics.counter("faults.injected").inc()

    def _finish(self, req: Request) -> RequestTiming:
        """Stamp the terminal transition: freeze the request's derived
        timing, emit the terminal instant, close its span track, and
        feed the latency histograms. Called exactly once per request
        (every terminal path funnels through _fail or _release)."""
        req.t_end = self._now()
        tm = RequestTiming.from_stamps(
            req.rid, req.state, t_submit=req.t_submit,
            t_admit=req.t_admit, t_first=req.t_first,
            tok_times=req.tok_times, t_end=req.t_end)
        req._timing = tm
        if self.obs is not None:
            tr = self.obs.tracer
            tr.instant(req.rid, "terminal", t=req.t_end, status=req.state,
                       n_tokens=tm.n_tokens)
            tr.close_track(req.rid, t=req.t_end, status=req.state)
            m = self.obs.metrics
            m.counter(f"sched.terminal.{req.state}").inc()
            if tm.n_tokens:
                m.histogram("sched.ttft_ms").observe(tm.ttft_ms)
                m.histogram("sched.tbt_ms_p99").observe(tm.tbt_ms_p99)
        return tm

    def _obs_sample(self) -> None:
        """Once per tick: mirror the pool/queue/tree state into gauges
        and append every instrument to its ring buffer. At numeric
        level (``REPRO_OBS=2``) also the device-reading health scans:
        pool NaR words and TP error-feedback residual norms."""
        m = self.obs.metrics
        st = self.pool.stats()
        m.gauge("pool.free").set(st.free)
        m.gauge("pool.in_use").set(st.in_use)
        m.gauge("pool.peak_in_use").set(st.peak_in_use)
        m.gauge("pool.shared_pages").set(st.shared_pages)
        m.gauge("pool.prefix_hit_tokens").set(st.prefix_hit_tokens)
        m.gauge("pool.quarantined").set(st.quarantined)
        m.gauge("sched.queue_depth").set(len(self._queue))
        m.gauge("sched.batch_active").set(
            sum(1 for s in self._slots
                if s is not None and s.state == "active"))
        m.gauge("sched.batch_prefilling").set(
            sum(1 for s in self._slots
                if s is not None and s.state == "prefilling"))
        if self.prefix is not None:
            for key, val in self.prefix.stats().items():
                m.gauge(f"prefix.{key}").set(val)
        if self.obs.numeric and self.pool.cache is not None:
            m.gauge("pool.nar_words").set(self.pool.scan_nar())
            from repro.dist.tp import residual_norms
            for site, norm in residual_norms(self.pool.cache).items():
                m.gauge(f"tp.res_norm/{site}").set(norm)
        m.sample(self._tick)

    def trace_records(self, meta: Optional[dict] = None) -> List[dict]:
        """The run's trace as JSONL-shaped records (spans + instants +
        one ``timing`` record per terminal request still remembered).
        Raises unless ``REPRO_OBS`` enabled tracing at construction."""
        if self.obs is None:
            raise RuntimeError("tracing is off: construct the scheduler "
                               "with REPRO_OBS=1 (or 2)")
        from repro.obs import export
        timings = [r._timing for r in self._requests.values()
                   if r._timing is not None]
        info = {"page_size": self.page_size,
                "num_pages": self.pool.num_pages,
                "decode_batch": self.decode_batch,
                "kv_quant": self.pool.spec.name}
        info.update(meta or {})
        return export.trace_records(self.obs.tracer, timings, meta=info)

    # -- failure paths -----------------------------------------------------

    def _fail(self, req: Request, status: str) -> None:
        """Terminate ``req`` with a failure ``status``: drop it from
        the queue or its decode slot, unreference its pages (COW
        refcounts honored — shared pages live on under their other
        owners), commit the cleared block-table row to the device, and
        buffer the terminal stream event."""
        if req.state == "queued":
            self._queue.remove(req)
        for p in req.pages:
            self.pool.unref(p)
        req.pages = ()
        req._contig = None
        if req.slot >= 0:
            self.pool.clear(req.slot)
            self._slots[req.slot] = None
            req.slot = -1
            # the freed pages may be reallocated this very tick: the
            # device table must not keep them installed for this slot
            self.pool.push_tables()
        req.state = status
        tm = self._finish(req)
        self._pending.append(StreamEvent(req.rid, -1, True, status,
                                         t=req.t_end, timing=tm))

    def _poison(self, req: Request) -> None:
        """Fail ``req`` as poisoned and quarantine every page of its
        block table (private *and* shared — corruption detected in its
        logits cannot be localized to one page, so its whole working
        set is retired; lossy for sharers, never unsafe). Quarantine
        runs *before* tree eviction and page release: the unrefs must
        retire these pages, not recycle them."""
        pages = set(req.pages)
        for p in pages:
            self.pool.quarantine(p)
        if self.prefix is not None:
            self.prefix.evict_pages(pages)
        if self.obs is not None:
            self.obs.tracer.instant(req.rid, "quarantine",
                                    pages=sorted(pages))
        self._fail(req, "poisoned")

    def _check_deadlines(self) -> None:
        now = self._now()
        for req in list(self._requests.values()):
            if (not req.done and req.deadline is not None
                    and now >= req.deadline):
                self._fail(req, "timeout")

    def _heartbeat(self) -> None:
        """One scheduler-liveness beat per tick into the watchdog: an
        external observer (another thread, an operator loop) calls
        :meth:`stalled` — if a compiled step wedges, beats stop and the
        watchdog reports the scheduler dead after ``stall_after``."""
        now = self._now()
        prev = self.watchdog.last.get(0)
        self.watchdog.beat(Heartbeat(
            host=0, step=self._tick, t=now,
            step_time=now - prev.t if prev is not None else 0.0))

    def stalled(self) -> bool:
        """Whether the serving loop has stopped beating (no tick for
        longer than ``stall_after`` on the scheduler clock)."""
        return not self.watchdog.healthy()

    # -- admission ---------------------------------------------------------

    def _effective_priority(self, req: Request) -> int:
        return req.priority + (self._tick - req.submit_tick) // AGING_TICKS

    def _admit(self) -> None:
        """Admit queued requests in effective-priority order while a
        slot and their worst-case pages can be secured: take references
        on the radix tree's shared prefix pages, evict cold tree leaves
        if the private remainder is short, allocate it, and seed the
        request's private contiguous cache with the shared prefix KV
        (``gather_prefix`` — wire words copied as words, bit-exact).

        Stops at the first request that does not fit (head-of-line
        blocking by design: admitting smaller later requests first
        would starve large ones — aging already orders the queue),
        *unless* preemption can make room: a running request with
        strictly lower effective priority is preempted (pages released,
        requeued with its generated tokens as prompt extension) and
        admission retries."""
        while self._queue:
            order = sorted(self._queue,
                           key=lambda r: (-self._effective_priority(r),
                                          r.rid))
            req = order[0]
            slot = next((i for i, s in enumerate(self._slots) if s is None),
                        None)
            if slot is None or not self._secure_pages(req):
                if (self.preempt and
                        self._preempt_for(self._effective_priority(req))):
                    continue
                if slot is not None and all(s is None for s in self._slots):
                    # a free slot, nothing running to ever release pages,
                    # and the tree already evicted as far as it can
                    # (_secure_pages ran evict_for): the pool — shrunk
                    # by quarantine — can never serve this request.
                    # Fail it definitively instead of spinning forever.
                    self._fail(req, "cancelled")
                    continue
                return
            self._queue.remove(req)
            self._start_prefill(req, slot)

    def _preempt_for(self, min_eff: int) -> bool:
        """Preempt the lowest-effective-priority running request if it
        is *strictly* below ``min_eff`` (never preempt for an equal or
        lower candidate — that would ping-pong). Youngest rid breaks
        ties. Returns whether a victim was preempted."""
        running = [s for s in self._slots if s is not None]
        if not running:
            return False
        victim = min(running, key=lambda r: (self._effective_priority(r),
                                             -r.rid))
        if self._effective_priority(victim) >= min_eff:
            return False
        self._preempt(victim)
        return True

    def _preempt(self, req: Request) -> None:
        """Kick ``req`` out of its slot back onto the queue: pages
        unreferenced (tree-donated pages survive under the radix tree,
        so re-admission largely re-*references* instead of recomputes),
        generated tokens kept — they rejoin as a prompt extension via
        ``prefill_tokens``. ``submit_tick`` resets so aging restarts:
        a fresh victim cannot immediately age past its preemptor."""
        for p in req.pages:
            self.pool.unref(p)
        req.pages = ()
        req._contig = None
        req._cursor = 0
        req._first_page = 0
        if req.slot >= 0:
            self.pool.clear(req.slot)
            self._slots[req.slot] = None
            req.slot = -1
            self.pool.push_tables()
        req.state = "queued"
        req.submit_tick = self._tick
        self._queue.append(req)
        self.preemptions += 1
        if self.obs is not None:
            tr = self.obs.tracer
            now = self._now()
            # close the phase spans but keep the "request" root open —
            # the lifecycle continues; re-admission re-enters "queued"
            tr.close_track(req.rid, t=now, keep=1, preempted=True)
            tr.instant(req.rid, "preempt", t=now, tick=self._tick,
                       generated=len(req.generated))
            tr.begin(req.rid, "queued", t=now, requeue=True)
            self.obs.metrics.counter("sched.preemptions").inc()

    def _secure_pages(self, req: Request) -> bool:
        """Reserve ``req``'s worst-case pages: shared prefix pages by
        reference, the private remainder from the free list (evicting
        LRU tree leaves as needed). On success ``req.pages`` holds the
        full page list (shared head + private tail) and ``req._cursor``/
        ``req._first_page`` mark where prefill starts. Planning runs
        over ``prefill_tokens``: a preempted request's earlier tree
        donations (prompt *and* generated pages) count as prefix hits
        on re-admission."""
        pool = self.pool
        stream = req.prefill_tokens
        plan = (self.prefix.plan(stream) if self.prefix is not None
                else PrefixPlan(shared=(), cow_src=None, suffix_start=0))
        n_private = req.pages_needed - len(plan.shared)
        if self.prefix is not None:
            self.prefix.acquire(stream, plan)
            if plan.cow_src is not None:
                # pin the carved-out page for the gather below — eviction
                # under page pressure must not free what we are reading
                pool.ref(plan.cow_src)
            self.prefix.evict_for(n_private)
        if pool.pages_free() < n_private:
            if self.prefix is not None:
                if plan.cow_src is not None:
                    pool.unref(plan.cow_src)
                for p in plan.shared:
                    pool.unref(p)
            return False
        private = pool.alloc(n_private)
        req.pages = plan.shared + private
        req._cursor = plan.suffix_start
        req._first_page = plan.suffix_start // self.page_size
        if plan.hit_tokens:
            pool.note_prefix_hits(plan.hit_tokens)
        self._plan_gather = (plan, req)
        return True

    def _start_prefill(self, req: Request, slot: int) -> None:
        """Build the request's private contiguous prefill cache, seeded
        with the shared prefix pages (and, on a full-hit COW, the
        carved-out source page — copied, then unpinned)."""
        from repro.models import model
        eng = self.engine
        plan, _ = self._plan_gather
        ps = self.page_size
        plen = len(req.prefill_tokens)
        # one slack page past the prompt pages: the final (or COW) chunk
        # is right-padded to ps, and its padding appends may run past
        # the prompt bucket — dynamic_update_slice must never clamp
        width = (pages_for(plen, ps) + 1) * ps
        contig = model.init_cache(eng.cfg, batch=1, max_len=width)
        gather = plan.shared + ((plan.cow_src,)
                                if plan.cow_src is not None else ())
        self.pool.gather_prefix(contig, gather, pos=plan.suffix_start)
        if plan.cow_src is not None:
            self.pool.unref(plan.cow_src)
        req._contig = contig
        req.state = "prefilling"
        req.slot = slot
        self._slots[slot] = req
        self._plan_gather = None
        now = self._now()
        if req.t_admit is None:    # first admission only: a preempted
            req.t_admit = now      # request keeps its original queue_ms
        if self.obs is not None:
            tr = self.obs.tracer
            tr.end(req.rid, "queued", t=now)
            tr.begin(req.rid, "prefill", t=now, slot=slot, plen=plen,
                     cursor=req._cursor)
            if plan.hit_tokens:
                tr.instant(req.rid, "prefix_hit", t=now,
                           tokens=plan.hit_tokens,
                           shared_pages=len(plan.shared),
                           cow=plan.cow_src is not None)

    # -- chunked prefill ---------------------------------------------------

    def _request_key(self, req: Request):
        import jax
        if req._key is None:
            base = jax.random.PRNGKey(self.engine.seed if req.seed is None
                                      else req.seed)
            req._key = (base if req.seed is not None
                        else jax.random.fold_in(base, req.rid))
        return req._key

    def _prefill_tick(self) -> Iterator[StreamEvent]:
        """One ``page_size`` chunk for every prefilling slot. A request
        whose last chunk lands samples its next token, scatters its
        computed pages into the pool, donates its full prefill pages to
        the radix tree, and joins the decode batch. (For a fresh
        request the prefill stream is its prompt and the sampled token
        is token 0; a *resumed* request prefills prompt + generated and
        the sample continues exactly where decode left off — same
        logits position, same persisted PRNG key.)

        NaN in the completion logits (a quarantine-worthy corrupted
        page gathered from the prefix tree, or injected into the pool
        mid-prefill) poisons the request here, before it ever joins the
        decode batch.

        Events are buffered and yielded only after ``push_tables`` has
        committed the new device state: a consumer that abandons the
        stream mid-yield must never leave host bookkeeping ahead of the
        device cache."""
        events = []
        for slot in range(self.decode_batch):
            req = self._slots[slot]
            if req is None or req.state != "prefilling":
                continue
            stream = req.prefill_tokens
            chunk = stream[req._cursor:req._cursor + self.page_size]
            with phase("prefill_chunk", self._tracer, pos=req._cursor,
                       n=len(chunk)):
                row = self._dispatch_chunk(req, chunk)
            if req._cursor < len(stream):
                continue
            with phase("prefill_sync", self._tracer):
                tok0 = self._sample_prefilled(req, row)
            if tok0 is None:
                # corrupted wire words reached these logits: NaR decode
                # pins corruption -> NaN, so this request is poisoned
                self._poison(req)
                continue
            with phase("prefill_commit", self._tracer):
                events.append(self._activate(slot, req, stream, tok0))
        if events:
            with phase("prefill_commit", self._tracer):
                self.pool.push_tables()
        yield from events

    def _dispatch_chunk(self, req: Request, chunk: List[int]):
        """Enqueue the next prefill chunk of ``req`` (right-padded to
        ``page_size``) on its contiguous cache; returns the chunk's
        last-position logits row (a device array, not read here)."""
        import jax.numpy as jnp
        eng = self.engine
        tokens = np.zeros((1, self.page_size), np.int32)
        tokens[0, :len(chunk)] = chunk
        if self.obs is not None:
            self.obs.tracer.begin(req.rid, "chunk", pos=req._cursor,
                                  n=len(chunk))
        row, req._contig = eng._prefill_chunk(
            eng.params, jnp.asarray(tokens), req._contig,
            jnp.asarray(req._cursor, jnp.int32),
            jnp.asarray(len(chunk) - 1, jnp.int32))
        req._cursor += len(chunk)
        if self.obs is not None:
            self.obs.tracer.end(req.rid, "chunk")
        return row

    def _sample_prefilled(self, req: Request, row) -> Optional[int]:
        """The token a finished prefill samples from its last logits
        ``row`` under the request's policy (the key advances when
        sampled), or ``None`` when the logits hold NaN. Two host reads:
        the NaN check, then the sampled token."""
        import jax.numpy as jnp
        if bool(np.isnan(np.asarray(row)).any()):
            return None
        if req.temperature > 0.0:
            keys = self._request_key(req)[None]
        else:
            keys = jnp.zeros((1, 2), jnp.uint32)
        toks, new_keys = self.engine._sample_rows(
            row, keys, jnp.asarray([req.temperature], jnp.float32),
            jnp.asarray([req.top_p], jnp.float32))
        if req.temperature > 0.0:
            req._key = new_keys[0]
        return int(np.asarray(toks)[0])

    def _activate(self, slot: int, req: Request, stream: List[int],
                  tok0: int) -> StreamEvent:
        """Move a finished prefill into the decode batch: scatter its
        computed pages into the pool, donate its full prefill pages to
        the radix tree, point ``slot`` at its pages (the caller pushes
        the tables) and record its first token."""
        ps = self.page_size
        plen = len(stream)
        self.pool.scatter_prefill(
            req._contig, req.pages[req._first_page:pages_for(plen, ps)],
            first_page=req._first_page)
        req._contig = None
        if self.prefix is not None:
            self.prefix.insert(stream, req.pages[:plen // ps])
        req.state = "active"
        req.generated.append(tok0)
        now = self._now()
        req.t_first = now
        req.tok_times.append(now)
        self.pool.assign(slot, req.pages, pos=plen)
        if self.obs is not None:
            tr = self.obs.tracer
            tr.end(req.rid, "prefill", t=now)
            tr.instant(req.rid, "first_token", t=now, token=tok0)
            tr.begin(req.rid, "decode", t=now)
            self.obs.metrics.counter("sched.tokens").inc()
        done = tok0 == req.eos_id or len(req.generated) >= req.max_new
        tm = None
        if done:
            self._release(req)
            tm = self._finish(req)
        return StreamEvent(req.rid, tok0, done, t=now, timing=tm)

    # -- packed decode -----------------------------------------------------

    def _decode_step(self) -> Iterator[StreamEvent]:
        """One compiled step for every active slot — per-slot sampling
        state rides along; release finished sequences' pages the same
        step."""
        active = [i for i, s in enumerate(self._slots)
                  if s is not None and s.state == "active"]
        if not active:
            return
        with phase("decode_dispatch", self._tracer, rows=len(active)):
            tok_next, bad = self._dispatch_decode(active)
        with phase("decode_sync", self._tracer):
            # this read blocks on the step just dispatched — the
            # deliberate price of *same-step* page release and admission
            # (the whole point of the paged pool); the lockstep loop,
            # which never releases mid-batch, pipelines with a
            # one-step-stale read instead (engine.generate_lockstep)
            toks = np.asarray(tok_next)
            # NaN-in-logits per batch row, read only for *active* rows
            # (idle and prefilling slots ride the scratch page and may be
            # NaN legitimately): a bad row means this request's
            # block-table pages fed corruption into its logits — poison
            # exactly it
            bad_rows = np.asarray(bad)
        with phase("decode_commit", self._tracer):
            events = self._commit_decode(active, toks, bad_rows)
        yield from events

    def _dispatch_decode(self, active: List[int]):
        """Enqueue one paged decode step over every slot (the ``active``
        ones carry their last token and sampling state) and advance the
        pool's host mirrors; returns the step's device ``(tokens, NaN
        rows)``, not read here."""
        import jax.numpy as jnp
        eng = self.engine
        w = self.decode_batch
        tok = np.zeros((w, 1), np.int32)
        temps = np.zeros((w,), np.float32)
        top_ps = np.ones((w,), np.float32)
        zero_key = jnp.zeros((2,), jnp.uint32)
        key_rows = [zero_key] * w
        for i in active:
            req = self._slots[i]
            tok[i, 0] = req.generated[-1]
            temps[i] = req.temperature
            top_ps[i] = req.top_p
            if req.temperature > 0.0:
                key_rows[i] = self._request_key(req)
        # snapshot pos: the pool mutates its host mirror in place right
        # after dispatch (advance), and a zero-copy transfer would alias
        pos = jnp.asarray(self.pool.pos[:, None].copy())  # (W, 1) RoPE
        tok_next, cache, new_keys, bad = eng._step_paged(
            eng.params, jnp.asarray(tok), self.pool.cache, pos,
            jnp.stack(key_rows), jnp.asarray(temps), jnp.asarray(top_ps))
        self.pool.cache = cache
        self.pool.advance(active)
        for i in active:
            req = self._slots[i]
            if req.temperature > 0.0:
                req._key = new_keys[i]
        return tok_next, bad

    def _commit_decode(self, active: List[int], toks,
                       bad_rows) -> List[StreamEvent]:
        """Record the step's tokens read on the host: poison rows whose
        logits held NaN, append each token, release finished requests
        and commit their cleared slots; returns the stream events."""
        # one clock read shared by every row: the step's tokens all
        # became host-visible at the same blocking read
        now = self._now()
        events = []
        released = False
        for i in active:
            req = self._slots[i]
            if bad_rows[i]:
                self._poison(req)
                continue
            tk = int(toks[i, 0])
            req.generated.append(tk)
            if req.t_first is None:
                req.t_first = now
            req.tok_times.append(now)
            if self.obs is not None:
                self.obs.tracer.instant(req.rid, "token", t=now, token=tk)
                self.obs.metrics.counter("sched.tokens").inc()
            done = tk == req.eos_id or len(req.generated) >= req.max_new
            tm = None
            if done:
                self._release(req)
                released = True
                tm = self._finish(req)
            events.append(StreamEvent(req.rid, tk, done,
                                      t=now, timing=tm))
        if released:
            # commit the cleared slots before any yield: an abandoned
            # stream must not resume with freed (and possibly
            # reallocated) pages still installed on the device
            self.pool.push_tables()
        return events

    def _release(self, req: Request) -> None:
        """Unreference the request's pages and free its slot the step
        it finishes. Private pages return to the free list; pages the
        prefix tree also holds live on as shared prompt prefix."""
        for p in req.pages:
            self.pool.unref(p)
        if req.slot >= 0:
            self.pool.clear(req.slot)
            self._slots[req.slot] = None
        req.state = "done"
