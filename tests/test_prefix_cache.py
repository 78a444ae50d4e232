"""Property harness for refcounted prefix sharing (pool + radix tree).

The prefix cache turns the page allocator from exclusive ownership into
reference counting: a page can be held by the radix tree and any number
of block tables at once, and copy-on-write carves exactly one page out
of a full-hit prompt. None of that needs a device — these tests drive
``PagePool(alloc_device=False)`` and :class:`PrefixCache` through a
host-side mirror of the scheduler's admission/insert/release
bookkeeping and assert, after **every** operation of a randomized
schedule:

  * free + in-use is an exact partition of the non-scratch pages;
  * no page sits on the free list while anything references it;
  * a page appearing in two block tables (or a table and the tree)
    always carries the matching refcount — exact equality, not >=;
  * a full-hit (COW) admission recomputes exactly one prompt page;
  * ``shared_pages`` counts pages with >1 owner, and ``hbm_bytes``
    counts every physical page once no matter how shared it is;
  * draining every request and clearing the tree returns the pool to
    completely full;
  * the fill counters (``PageStats.fill_tokens``/``fill_capacity``)
    equal the positions written and held by the rows of every decode
    step, and never read above full.

Across the module the randomized tests run >= 200 schedules (see
``max_examples`` totals) under the hypcompat shim.
"""

import collections
import dataclasses
import itertools

import pytest

from hypcompat import given, settings, st

from repro.configs import get_arch
from repro.serve.paged import PagePool, PagePoolError, pages_for
from repro.serve.prefix import PrefixCache

PS = 8                                  # page size for every sim below
# three token streams that agree nowhere: prompts cut from one stream
# share prefixes at page granularity, prompts from different streams
# diverge in page 0
BASES = [[(17 * k + 3 * i + 1) % 6 for i in range(4 * PS)]
         for k in range(3)]


def _pool(num_pages, page_size=PS, batch=4, max_pages=8):
    cfg = dataclasses.replace(get_arch("phi3-medium-14b").reduced,
                              kv_quant="takum8")
    return PagePool(cfg, batch=batch, num_pages=num_pages,
                    page_size=page_size, max_pages=max_pages,
                    alloc_device=False)


class _Sim:
    """Host mirror of the scheduler's page bookkeeping: admission
    (plan / acquire / evict / alloc, with rollback), the post-prefill
    donation to the tree, and release. No device work, no tokens —
    just the ownership protocol the real scheduler follows."""

    def __init__(self, num_pages):
        self.pool = _pool(num_pages)
        self.prefix = PrefixCache(self.pool)
        self.live = {}
        self._rids = itertools.count()
        self.plans = 0                  # our side of the lookups ledger
        # decode rows: rid -> [slot, decode steps left]; our side of the
        # fill counters
        self.rows = {}
        self.fill_tokens = self.fill_capacity = 0

    def plan(self, prompt):
        self.plans += 1
        return self.prefix.plan(prompt)

    def admit(self, prompt, max_new):
        ps = self.pool.page_size
        plan = self.plan(prompt)
        needed = pages_for(len(prompt) + max_new - 1, ps)
        n_private = needed - len(plan.shared)
        assert n_private >= 1, "admission always computes >= 1 page"
        if plan.cow_src is not None:
            # the COW copy is the only prompt page not served from cache
            assert pages_for(len(prompt), ps) - len(plan.shared) == 1, \
                "full hit must recompute exactly one prompt page"
        self.prefix.acquire(prompt, plan)
        if plan.cow_src is not None:
            self.pool.ref(plan.cow_src)     # pin across eviction + gather
        self.prefix.evict_for(n_private)
        if self.pool.pages_free() < n_private:
            for p in plan.shared:
                self.pool.unref(p)
            if plan.cow_src is not None:
                self.pool.unref(plan.cow_src)
            return None
        private = self.pool.alloc(n_private)
        if plan.cow_src is not None:
            self.pool.unref(plan.cow_src)   # gather done, pin released
        pages = list(plan.shared) + list(private)
        # "prefill finished": donate the full prompt pages to the tree
        self.prefix.insert(prompt, pages[:len(prompt) // ps])
        rid = next(self._rids)
        self.live[rid] = pages
        taken = {slot for slot, _ in self.rows.values()}
        free = [s for s in range(self.pool.batch) if s not in taken]
        if free:
            self.pool.assign(free[0], pages, pos=len(prompt))
            self.rows[rid] = [free[0], max_new - 1]
        return rid

    def release(self, rid):
        for p in self.live.pop(rid):
            self.pool.unref(p)
        if rid in self.rows:
            self.pool.clear(self.rows.pop(rid)[0])

    def decode_step(self):
        """One decode step over the rows with steps left: each writes
        one position, which must lie inside the pages it holds."""
        ps = self.pool.page_size
        active = sorted((slot, rid) for rid, (slot, left)
                        in self.rows.items() if left > 0)
        for slot, rid in active:
            written = int(self.pool.pos[slot]) + 1
            held = len(self.live[rid]) * ps
            assert written <= held, "a decode write past the row's pages"
            self.fill_tokens += written
            self.fill_capacity += held
            self.rows[rid][1] -= 1
        self.pool.advance([slot for slot, _ in active])


def _tree_pages(prefix):
    pages = []
    stack = list(prefix._root.values())
    while stack:
        node = stack.pop()
        pages.append(node.page)
        stack.extend(node.children.values())
    return pages


def _check(sim):
    """The full invariant battery, run after every schedule step."""
    pool = sim.pool
    tree_pages = _tree_pages(sim.prefix)
    assert len(tree_pages) == len(set(tree_pages)), \
        "tree holds one node (one ref) per page"
    assert sim.prefix.pages_held() == len(tree_pages)
    expected = collections.Counter(tree_pages)
    for pages in sim.live.values():
        assert len(pages) == len(set(pages)), "table references a page twice"
        expected.update(pages)
    in_use = set(expected)
    assert 0 not in in_use, "scratch page leaked into a table or the tree"
    # exact refcount equality: every owner is accounted, nothing more
    for p in in_use:
        assert pool.refcount(p) == expected[p], (p, expected[p])
    # partition of the non-scratch pages, shared pages counted once
    assert pool.pages_in_use() == len(in_use)
    assert pool.pages_free() + pool.pages_in_use() == pool.num_pages - 1
    # no page is simultaneously free and referenced: draining the free
    # list must never hand out a page somebody still owns
    drained = pool.alloc(pool.pages_free())
    assert not (set(drained) & in_use), "free list held a referenced page"
    pool.free(drained)
    # sharing accounting
    assert pool.shared_pages() == sum(1 for p in in_use if expected[p] > 1)
    stats = pool.stats()
    assert stats.shared_pages == pool.shared_pages()
    # hbm bytes are physical: independent of how many owners a page has
    assert pool.hbm_bytes() == pool.num_pages * pool.page_hbm_bytes()
    # logical pages (sum of table + tree views) >= physical in-use;
    # strictly greater exactly when sharing is active
    logical = sum(expected.values())
    assert logical >= pool.pages_in_use()
    if pool.shared_pages():
        assert logical > pool.pages_in_use()
    # the tree-traffic ledger (what the obs `prefix.*` gauges mirror):
    # insert/evict counters reconcile with the live node count exactly,
    # and every plan() call is one lookup, hit or not
    tree = sim.prefix.stats()
    assert tree["nodes"] == len(tree_pages)
    assert tree["nodes_inserted"] - tree["nodes_evicted"] == tree["nodes"]
    assert tree["lookups"] == sim.plans
    assert 0 <= tree["hits"] <= tree["lookups"]
    assert (tree["hit_tokens"] == 0) == (tree["hits"] == 0)
    # the fill counters: exactly the rows' written and held positions
    assert (stats.fill_tokens, stats.fill_capacity) == \
        (sim.fill_tokens, sim.fill_capacity)
    assert 0 <= stats.fill_tokens <= stats.fill_capacity


def _prompt(a, b):
    plen = 1 + (a * 7 + b * 3) % (4 * PS)
    return BASES[a % 3][:plen]


# ---------------------------------------------------------------------------
# the main property: random submit/release/evict/clear schedules
# ---------------------------------------------------------------------------


@settings(max_examples=140, deadline=None)
@given(num_pages=st.integers(6, 24),
       schedule=st.lists(st.tuples(st.integers(0, 9), st.integers(0, 7),
                                   st.integers(0, 7)),
                         min_size=4, max_size=40))
def test_refcount_invariants_under_random_schedule(num_pages, schedule):
    """op <= 4 submits a prompt cut from a shared base stream (lengths
    hit mid-page, exact-page and full-hit shapes); op 5-6 releases a
    live request; op 7 evicts one LRU leaf; op 8 clears the tree; op 9
    resubmits an earlier prompt verbatim (forcing warm full hits and
    the COW path). Invariants checked after every step; the schedule
    ends with a drain that must refill the pool completely."""
    sim = _Sim(num_pages)
    history = []
    for op, a, b in schedule:
        if op <= 4:
            prompt = _prompt(a, b)
            history.append(prompt)
            sim.admit(prompt, max_new=1 + b % 6)
        elif op in (5, 6) and sim.live:
            rids = sorted(sim.live)
            sim.release(rids[b % len(rids)])
        elif op == 7:
            sim.prefix.evict_one()
        elif op == 8:
            sim.prefix.clear()
        elif op == 9 and history:
            sim.admit(history[b % len(history)], max_new=1 + a % 6)
        sim.decode_step()
        _check(sim)
    for rid in sorted(sim.live):
        sim.release(rid)
        _check(sim)
    sim.prefix.clear()
    assert sim.pool.pages_in_use() == 0
    assert sim.pool.pages_free() == num_pages - 1, "drain must refill pool"


# ---------------------------------------------------------------------------
# quarantine: poisoned pages leave circulation, partition gains "retired"
# ---------------------------------------------------------------------------


def _poison(sim, rid):
    """The scheduler's poison protocol, mirrored: quarantine FIRST (so
    every subsequent unref retires instead of recycles), then evict the
    corrupted subtrees from the radix tree, then release the owner."""
    pages = set(sim.live[rid])
    for p in pages:
        sim.pool.quarantine(p)
    sim.prefix.evict_pages(pages)
    sim.release(rid)


def _check_quarantine(sim):
    """Quarantine-aware invariant battery. The two-way free/in-use
    partition becomes three-way: retired pages (quarantined with no
    remaining owners) are in neither set, and neither the free list nor
    the radix tree may ever serve a quarantined page."""
    pool = sim.pool
    tree_pages = _tree_pages(sim.prefix)
    quarantined = set(pool.quarantined_pages())
    assert not (set(tree_pages) & quarantined), \
        "radix tree still serves a quarantined page"
    expected = collections.Counter(tree_pages)
    for pages in sim.live.values():
        expected.update(pages)
    in_use = set(expected)
    assert 0 not in in_use and 0 not in quarantined
    for p in in_use:
        assert pool.refcount(p) == expected[p], (p, expected[p])
    retired = len(quarantined - in_use)
    assert pool.pages_in_use() == len(in_use)
    assert pool.pages_free() + pool.pages_in_use() + retired \
        == pool.num_pages - 1, "free/in-use/retired must partition the pool"
    assert pool.stats().quarantined == len(quarantined)
    drained = pool.alloc(pool.pages_free())
    assert not (set(drained) & quarantined), \
        "free list handed out a quarantined page"
    assert not (set(drained) & in_use)
    pool.free(drained)


@settings(max_examples=80, deadline=None)
@given(num_pages=st.integers(6, 24),
       schedule=st.lists(st.tuples(st.integers(0, 9), st.integers(0, 7),
                                   st.integers(0, 7)),
                         min_size=4, max_size=40))
def test_quarantine_invariants_under_random_schedule(num_pages, schedule):
    """The refcount schedule with poison in the mix: op <= 4 submits,
    op 5 releases, op 6 poisons a live request (quarantine + tree evict
    + release), op 7 evicts an LRU leaf, op 8 clears the tree and
    sometimes runs the operator repair hook, op 9 resubmits an earlier
    prompt (warm hits over a tree that may have lost subtrees). After
    the final drain, release_quarantined() must refill the pool
    completely — no page is ever leaked, even through poisoning."""
    sim = _Sim(num_pages)
    history = []
    for op, a, b in schedule:
        if op <= 4:
            prompt = _prompt(a, b)
            history.append(prompt)
            sim.admit(prompt, max_new=1 + b % 6)
        elif op == 5 and sim.live:
            rids = sorted(sim.live)
            sim.release(rids[b % len(rids)])
        elif op == 6 and sim.live:
            rids = sorted(sim.live)
            _poison(sim, rids[b % len(rids)])
        elif op == 7:
            sim.prefix.evict_one()
        elif op == 8:
            sim.prefix.clear()
            if b % 2:
                sim.pool.release_quarantined()
        elif op == 9 and history:
            sim.admit(history[b % len(history)], max_new=1 + a % 6)
        _check_quarantine(sim)
    for rid in sorted(sim.live):
        sim.release(rid)
        _check_quarantine(sim)
    sim.prefix.clear()
    assert sim.pool.pages_in_use() == 0
    sim.pool.release_quarantined()
    assert sim.pool.pages_quarantined() == 0
    assert sim.pool.pages_free() == num_pages - 1, \
        "repair hook must refill the pool completely"


def test_quarantine_deterministic_lifecycle():
    pool = _pool(num_pages=8)
    with pytest.raises(PagePoolError, match="not a poolable"):
        pool.quarantine(0)                       # scratch page
    with pytest.raises(PagePoolError, match="not a poolable"):
        pool.quarantine(8)                       # beyond the pool
    pages = pool.alloc(3)
    p = pages[0]
    pool.quarantine(p)
    pool.quarantine(p)                           # idempotent
    assert pool.pages_quarantined() == 1
    assert pool.quarantined_pages() == frozenset({p})
    # still referenced: stays in-use, owners read it until they detect
    assert pool.pages_in_use() == 3
    assert pool.release_quarantined() == 0, "referenced pages stay put"
    pool.unref(p)                                # final owner: retire it
    assert pool.pages_in_use() == 2
    assert pool.pages_free() == 8 - 1 - 2 - 1    # scratch, live, retired
    # a currently-free page leaves the free list immediately
    free_page = next(iter(set(range(1, 8)) - set(pages)))
    before = pool.pages_free()
    pool.quarantine(free_page)
    assert pool.pages_free() == before - 1
    drained = pool.alloc(pool.pages_free())
    assert free_page not in drained and p not in drained
    pool.free(drained)
    # repair: both unreferenced quarantined pages return to circulation
    assert pool.release_quarantined() == 2
    assert pool.pages_quarantined() == 0
    assert pool.pages_free() == 8 - 1 - 2


def test_evict_pages_removes_whole_subtrees():
    """Evicting a corrupted page must also drop every descendant node:
    a child's KV was computed by attending to the corrupted ancestor, so
    a warm hit through it would serve poisoned state with a clean page
    id. The sibling stream shares no pages and must survive."""
    sim = _Sim(num_pages=24)
    long_p = BASES[0][:3 * PS]
    other = BASES[1][:PS]
    r1 = sim.admit(long_p, max_new=2)
    r2 = sim.admit(other, max_new=2)
    assert sim.prefix.pages_held() == 4          # 3-page chain + 1 node
    head = sim.live[r1][0]                       # root of the long chain
    removed = sim.prefix.evict_pages({head})
    assert removed == 3, "descendants of the corrupted page must go too"
    assert sim.prefix.pages_held() == 1          # the sibling stream
    # sibling still warm (full hit: all but the COW carve-out token)
    assert sim.plan(other).hit_tokens == PS - 1
    assert sim.plan(long_p).hit_tokens == 0
    # table refs survived the tree eviction; no quarantine in this test,
    # so releasing recycles the pages straight back to the free list
    _check(sim)
    sim.release(r1)
    sim.release(r2)
    sim.prefix.clear()
    assert sim.pool.pages_in_use() == 0
    assert sim.pool.pages_free() == 24 - 1


# ---------------------------------------------------------------------------
# plans: COW carves exactly one page, mid-page divergence carves none
# ---------------------------------------------------------------------------


@settings(max_examples=80, deadline=None)
@given(a=st.integers(0, 7), b=st.integers(0, 7), cut=st.integers(1, 31))
def test_plan_shapes_cold_warm_and_divergent(a, b, cut):
    """For any prompt: the cold plan shares nothing; the warm identical
    plan is a full hit sharing all but one page (the COW carve-out,
    suffix_start == plen - 1); a prompt truncated or diverged mid-tree
    shares exactly its full matched pages and recomputes from there."""
    sim = _Sim(num_pages=24)
    prompt = _prompt(a, b)
    plen = len(prompt)
    cold = sim.plan(prompt)
    assert cold.shared == () and cold.cow_src is None
    assert cold.suffix_start == 0 and cold.hit_tokens == 0
    rid = sim.admit(prompt, max_new=4)
    assert rid is not None

    warm = sim.plan(prompt)
    n_prompt_pages = plen // PS          # full pages the tree can hold
    if n_prompt_pages:
        # full hit: everything cached up to the last token's page
        if plen % PS == 0:
            assert warm.cow_src is not None
            assert len(warm.shared) == n_prompt_pages - 1
            assert warm.suffix_start == plen - 1
        else:
            # tail is sub-page: all full pages shared, no COW needed
            assert warm.cow_src is None
            assert len(warm.shared) == n_prompt_pages
            assert warm.suffix_start == n_prompt_pages * PS
    else:
        assert warm == cold              # sub-page prompt caches nothing

    # divergence: keep `cut` tokens, then leave the base alphabet (0..5)
    # entirely — the tail chunk can never match a cached node
    div = prompt[:cut] + [7] * PS
    dplan = sim.plan(div)
    full_match = min(cut, plen) // PS
    assert dplan.cow_src is None, "mid-page divergence never copies"
    assert len(dplan.shared) == full_match
    assert dplan.suffix_start == full_match * PS
    _check(sim)


# ---------------------------------------------------------------------------
# deterministic corners
# ---------------------------------------------------------------------------


def test_shared_page_survives_releasing_one_owner():
    sim = _Sim(num_pages=24)
    prompt = BASES[0][:3 * PS]
    r1 = sim.admit(prompt, max_new=4)
    r2 = sim.admit(prompt, max_new=4)            # warm: COW full hit
    shared = set(sim.live[r1]) & set(sim.live[r2])
    assert len(shared) == 2, "r2 shares all prompt pages but the carve-out"
    assert sim.pool.shared_pages() >= 2
    sim.release(r1)
    for p in shared:                             # r2 + tree still own these
        assert sim.pool.refcount(p) == 2
    _check(sim)
    sim.release(r2)
    _check(sim)
    sim.prefix.clear()
    assert sim.pool.pages_in_use() == 0


def test_divergent_copy_is_exactly_one_page():
    sim = _Sim(num_pages=24)
    prompt = BASES[1][:2 * PS]
    sim.admit(prompt, max_new=2)
    plan = sim.plan(prompt)
    assert plan.cow_src is not None
    before = sim.pool.pages_in_use()
    rid = sim.admit(prompt, max_new=1)           # 1 prompt copy + 0 extra
    # needed = pages_for(16 + 1 - 1, 8) = 2; one shared, one private copy
    assert sim.pool.pages_in_use() == before + 1
    assert len(sim.live[rid]) == 2
    _check(sim)


def test_eviction_of_live_page_only_ends_shareability():
    sim = _Sim(num_pages=24)
    prompt = BASES[2][:PS]
    rid = sim.admit(prompt, max_new=2)
    page = sim.live[rid][0]
    assert sim.pool.refcount(page) == 2          # table + tree
    while sim.prefix.evict_one():
        pass
    assert sim.pool.refcount(page) == 1, "table ref must survive eviction"
    _check(sim)
    sim.release(rid)
    assert sim.pool.pages_in_use() == 0


def test_ref_unref_misuse_raises():
    pool = _pool(num_pages=8)
    with pytest.raises(PagePoolError, match="not allocated"):
        pool.ref(0)                              # scratch page
    with pytest.raises(PagePoolError, match="not allocated"):
        pool.ref(5)                              # free page
    (p,) = pool.alloc(1)
    pool.ref(p)
    pool.unref(p)
    assert pool.pages_in_use() == 1              # still one owner
    pool.unref(p)
    assert pool.pages_in_use() == 0
    with pytest.raises(PagePoolError, match="not allocated"):
        pool.unref(p)                            # below zero
