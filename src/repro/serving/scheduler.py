"""Slot + page allocation and request scheduling for the serving engine.

The engine owns a fixed pool of ``n_slots`` cache slots (rows of the batched
decode cache).  Requests queue FIFO; whenever a slot frees up, the scheduler
admits the oldest waiting request.  Slot exhaustion therefore QUEUES work —
it never errors — and freed slots are recycled immediately, which is what
keeps the decode batch full under sustained traffic.

Paged mode adds a :class:`PageAllocator` over the engine's physical KV page
pool: admission is then gated on PAGES, not slots — a request is admitted
only when its actual need (``ceil((prompt + max_new) / page_size)`` pages,
reserved up front so decode can never strand mid-stream) fits the free
list, so total admitted concurrency tracks real footprints instead of
``n_slots`` worst-case reservations.  Page exhaustion queues exactly like
slot exhaustion; admission stays strictly FIFO (a large request at the head
waits rather than being bypassed — deterministic traces over throughput
tricks).

The allocator is REFCOUNTED: one physical page may back several slots'
block tables at once (shared prompt-prefix pages — see
:class:`PrefixIndex` and the engine's copy-on-write admission path).
``alloc`` grants fresh pages at refcount 1, ``acquire`` adds a reader (or
revives a cached, refcount-0 page off the free list with its contents
intact), and ``free`` decrements — a page returns to the free list only
when its LAST reader releases it.  Refcounting also structurally closes
the boolean-owned allocator's duplicate-free bug: a single ``free`` call
rejects duplicate ids before mutating anything, so a page can never be
pushed onto the free list twice and later granted to two slots (silent KV
aliasing).

The allocator additionally owns the WARM-CACHE eviction policy: pages
whose content is indexed (``mark_indexed``) become LRU-ordered cache
entries when their last reader releases them.  ``alloc`` grants clean
(unindexed) free pages first and only then EVICTS cached pages —
least-recently-used first, announced through ``on_evict`` so the owner
drops the matching :class:`PrefixIndex` keys in the same operation (a
``match`` can therefore never hit a page after a writer re-granted it).
``cache_budget`` caps how many refcount-0 pages stay matchable; the
excess is evicted eagerly, again LRU-first.  This replaces the PR-5
behavior where cached entries were dropped only when a writer happened
to re-grant the page (lowest-id-first, i.e. the warm cache decayed in an
order unrelated to its value).

Pure host-side bookkeeping: no jax imports, trivially unit-testable
(tests/test_scheduler.py).
"""

from __future__ import annotations

import collections
import dataclasses
import hashlib
import time
from typing import Callable, Deque, Iterator, List, Optional, Tuple

import numpy as np

__all__ = [
    "SlotAllocator",
    "PageAllocator",
    "PageGrant",
    "PrefixIndex",
    "Scheduler",
    "AdmissionPolicy",
    "RejectedOverload",
    "RoutingPolicy",
    "FailoverBudget",
]


class SlotAllocator:
    """Free-list allocator over ``n_slots`` cache slots.

    ``alloc`` returns the lowest free slot id (deterministic reuse order —
    important for reproducible traces) or None when exhausted.
    """

    def __init__(self, n_slots: int):
        if n_slots < 1:
            raise ValueError(f"n_slots must be >= 1, got {n_slots}")
        self.n_slots = n_slots
        self._free = list(range(n_slots - 1, -1, -1))  # stack, lowest id on top
        self._active = [False] * n_slots

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_active(self) -> int:
        return self.n_slots - len(self._free)

    def is_active(self, slot: int) -> bool:
        return self._active[slot]

    def alloc(self) -> Optional[int]:
        if not self._free:
            return None
        slot = self._free.pop()
        self._active[slot] = True
        return slot

    def free(self, slot: int) -> None:
        if not (0 <= slot < self.n_slots):
            raise ValueError(f"slot {slot} out of range [0, {self.n_slots})")
        if not self._active[slot]:
            raise ValueError(f"double free of slot {slot}")
        self._active[slot] = False
        # keep the free list sorted so reuse order stays deterministic
        self._free.append(slot)
        self._free.sort(reverse=True)


class PageAllocator:
    """Refcounted allocator over ``n_pages`` fixed-size KV-cache pages.

    ``alloc(n)`` is ALL-OR-NOTHING: it returns the ``n`` lowest free page
    ids at refcount 1 (deterministic reuse order, mirroring
    :class:`SlotAllocator`) or None — never a partial grant, so a request
    can never be admitted into a half-backed cache.  Pages are unit-sized,
    so the pool cannot fragment: any ``n <= n_free`` request succeeds.
    ``extend`` grows an existing allocation with the same all-or-nothing
    contract.

    ``acquire(p)`` adds one READER to page ``p``: a live page
    (refcount >= 1) gets one more reference; a cached page (refcount 0 —
    back on the free list, contents still intact because only a fresh
    ``alloc`` hands a page to a writer) is revived off the free list to
    refcount 1.  This is the substrate for shared prompt-prefix pages: a
    shared page is counted ONCE in ``n_used`` no matter how many block
    tables map it.

    ``free`` DECREMENTS: a page returns to the free list only when its
    last reader releases it.  A single call validates the WHOLE list —
    range, liveness, and no duplicate ids — before mutating anything.
    (The boolean-owned predecessor also validated before mutating, but
    had no duplicate check: ``free([p, p])`` passed ownership twice and
    pushed ``p`` onto the free list twice, so a later ``alloc`` granted
    the same physical page to two slots — silent KV aliasing.)

    ``peak_used`` is the allocator-owned high-water mark, raised inside
    the only two operations that can grow usage (``alloc`` / ``acquire``)
    — so peaks are observed no matter which engine path allocated
    (admission, chunked prefill, COW fork), rather than being sampled on
    one engine code path.  ``reset_peak`` re-arms it to CURRENT usage,
    not zero: pages held across a counter reset stay observed.

    WARM CACHE.  ``mark_indexed(pages)`` declares that a page's contents
    are keyed in a content index (:class:`PrefixIndex`); when such a
    page's last reader releases it, it becomes a CACHED entry — still on
    the free list, contents intact, tracked in LRU order.  ``alloc``
    then prefers clean (never-indexed) free pages and only EVICTS cached
    entries when the clean supply runs out, least-recently-used first;
    every eviction is announced through ``on_evict`` before the page is
    handed to the writer, so index keys and list entries die together
    and a later ``match`` can never alias rewritten storage.  Recency is
    CHAIN-AWARE: pages listed earlier in a ``free``/``mark_indexed``
    call are cached as more recent than later ones (callers pass
    block-table order, and a chained prefix index loses everything below
    a missing page — evicting a chain's deep tail costs a few matched
    pages, evicting its head costs the whole chain).
    ``cache_budget`` (None = unbounded) caps the number of resident
    cached entries; the excess is evicted eagerly on release.  The
    invariant the engine relies on: an indexed page at refcount 0 is
    ALWAYS a cached entry, so a page can never leave the index's control
    silently.  With ``mark_indexed`` never called the allocator behaves
    exactly like the PR-5 one (pure lowest-id-first reuse).
    """

    def __init__(
        self,
        n_pages: int,
        *,
        cache_budget: Optional[int] = None,
        on_evict: Optional[Callable[[List[int]], None]] = None,
    ):
        if n_pages < 0:
            raise ValueError(f"n_pages must be >= 0, got {n_pages}")
        if cache_budget is not None and cache_budget < 0:
            raise ValueError(f"cache_budget must be >= 0, got {cache_budget}")
        self.n_pages = n_pages
        self.cache_budget = cache_budget
        self.on_evict = on_evict
        # THREAD CONFINEMENT: every field below is owned by the engine
        # thread that drives step()/admit(); nothing here is read across
        # threads (the cluster monitor only polls derived counts via
        # Engine properties, documented there).  If allocator state ever
        # crosses a thread boundary, add a lock and `# guarded by:`
        # annotations so the lock-discipline pass + sanitizer cover it.
        self._free = list(range(n_pages - 1, -1, -1))  # stack, lowest id on top
        self._ref = [0] * n_pages
        self._peak = 0
        # LRU-ordered cached pages (ref 0, contents indexed): oldest first
        self._cached: "collections.OrderedDict[int, None]" = collections.OrderedDict()
        self._indexed: set = set()  # pages whose contents are index-keyed
        # pages-saved accounting: how many times each indexed page was
        # re-acquired through a prefix match (each hit is one page of
        # prefill the warm cache saved).  Eviction uses it as a COST-AWARE
        # weight on the LRU order: hot chains (system prompts re-matched
        # every admission) outlive cold ones even when less recent.
        self._hits: dict = {}
        self.evictions = 0

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_used(self) -> int:
        return self.n_pages - len(self._free)

    @property
    def n_cached(self) -> int:
        return len(self._cached)

    @property
    def peak_used(self) -> int:
        return self._peak

    def refcount(self, page: int) -> int:
        return self._ref[page]

    def reset_peak(self) -> None:
        self._peak = self.n_used

    def rollback_peak(self, peak: int) -> None:
        """Restore a pre-transaction high-water mark after an all-or-nothing
        reservation FAILED and every reference it took was rolled back.

        Without this, a reservation that acquires k shared pages and then
        fails its tail alloc would leave ``peak_used`` inflated by pages
        that never backed any admitted work — and the head-of-queue retry
        in the scheduler re-runs that transaction every step.  Only valid
        when usage is actually back to (or below) the saved mark.
        """
        if not (self.n_used <= peak <= self._peak):
            raise ValueError(
                f"rollback_peak({peak}) with n_used={self.n_used}, "
                f"peak_used={self._peak}: references were not rolled back"
            )
        self._peak = peak

    def mark_indexed(self, pages) -> None:
        """Declare that ``pages`` back content-index entries.

        A marked page that is (or later falls to) refcount 0 becomes a
        cached entry instead of an anonymous free page: ``alloc`` skips
        it while clean pages remain and announces its eviction through
        ``on_evict`` when it finally is re-granted.  Idempotent; marking
        an already-cached page refreshes its LRU recency.
        """
        pages = [int(p) for p in pages]
        for p in pages:
            if not (0 <= p < self.n_pages):
                raise ValueError(f"page {p} out of range [0, {self.n_pages})")
            self._indexed.add(p)
        # reverse order: see free() — earlier-listed pages outlive later ones
        for p in reversed(pages):
            if self._ref[p] == 0:
                self._cached.pop(p, None)
                self._cached[p] = None  # most-recently-used position
        self._enforce_budget()

    def flush_cache(self) -> None:
        """Forget every cached/indexed page WITHOUT counting evictions.

        For owner-initiated index resets (``Engine.reset_prefix_cache``):
        the owner clears its index itself, so no ``on_evict`` callback
        fires and the eviction counter stays a policy-pressure metric.
        """
        self._cached.clear()
        self._indexed.clear()
        self._hits.clear()

    def drop_cached(self, pages) -> int:
        """Explicitly forget specific cached/indexed pages (session close).

        The pages are already on the free list (refcount 0) — they simply
        stop being matchable and become clean free pages, reusable by the
        next writer with no eviction work.  Pages still referenced just
        lose their indexed mark (on release they free plain, not cached).
        No ``on_evict`` fires — the caller is the index owner and drops
        its own keys — and no eviction is counted (this is an explicit
        close, not cache pressure).  Returns how many cached entries died.
        """
        n = 0
        for p in pages:
            p = int(p)
            self._indexed.discard(p)
            self._hits.pop(p, None)
            if p in self._cached:
                del self._cached[p]
                n += 1
        return n

    def _evict_victim(self) -> int:
        """Pick + remove the next cached page to evict.

        COST-AWARE LRU: the victim is the cached page with the FEWEST
        rematch hits (pages historically saved by keeping it), oldest
        first within a hit count.  With no hits recorded this degrades to
        exact LRU (insertion order), the PR-7 policy.
        """
        victim = None
        best = None
        for p in self._cached:  # insertion order == LRU order
            score = self._hits.get(p, 0)
            if score == 0:
                victim = p  # oldest never-rematched page: cannot do better
                break
            if best is None or score < best:
                victim, best = p, score
        del self._cached[victim]
        self._indexed.discard(victim)
        self._hits.pop(victim, None)
        return victim

    def _enforce_budget(self) -> None:
        """Evict cached entries beyond ``cache_budget`` (stay on free list)."""
        if self.cache_budget is None:
            return
        evicted = []
        while len(self._cached) > self.cache_budget:
            evicted.append(self._evict_victim())
        if evicted:
            self.evictions += len(evicted)
            if self.on_evict is not None:
                self.on_evict(evicted)

    def alloc(self, n: int) -> Optional[List[int]]:
        if n < 0:
            raise ValueError(f"cannot alloc {n} pages")
        if n > len(self._free):
            return None
        if not self._cached or n == 0:
            pages = [self._free.pop() for _ in range(n)]
        else:
            # clean-first: spend never-indexed free pages (lowest id first)
            # before evicting warm-cache entries, LRU first
            clean = sorted(p for p in self._free if p not in self._cached)
            pages = clean[:n]
            evicted = []
            while len(pages) < n:
                page = self._evict_victim()  # cost-aware LRU
                evicted.append(page)
                pages.append(page)
            if evicted:
                self.evictions += len(evicted)
                if self.on_evict is not None:
                    # index keys die BEFORE the writer sees the page
                    self.on_evict(list(evicted))
            granted = set(pages)
            self._free = [p for p in self._free if p not in granted]
        for p in pages:
            self._ref[p] = 1
        self._peak = max(self._peak, self.n_used)
        return pages

    def acquire(self, page: int) -> bool:
        """Add a reader to ``page`` (share a live page / revive a cached one)."""
        if not (0 <= page < self.n_pages):
            return False
        if self._ref[page] == 0:
            # cached page: still on the free list, contents intact — revive
            try:
                self._free.remove(page)
            except ValueError:  # not free and not referenced: cannot happen
                return False
            self._ref[page] = 1
            self._cached.pop(page, None)  # live again; re-cached on release
            self._peak = max(self._peak, self.n_used)
        else:
            self._ref[page] += 1
        return True

    def record_saved(self, pages) -> None:
        """Credit one rematch hit per page: each was mapped instead of
        re-prefilled by an ADMITTED reservation (callers must not credit
        rolled-back transactions — a starved head-of-queue retry re-acquires
        its matches every step and would pump the weights for free).  The
        hit count is the cost-aware weight ``_evict_victim`` keeps hot
        chains resident by."""
        for p in pages:
            p = int(p)
            if p in self._indexed:
                self._hits[p] = self._hits.get(p, 0) + 1

    def extend(self, pages: List[int], n: int) -> Optional[List[int]]:
        """Grow an allocation in place by ``n`` pages (all-or-nothing).

        The engine's current admission policy reserves a request's whole
        footprint up front (no mid-stream growth, hence no preemption), so
        today only tests exercise this; it is the hook an incremental
        reservation policy (grow per decode block, preempt on failure)
        would build on.
        """
        more = self.alloc(n)
        if more is None:
            return None
        pages.extend(more)
        return pages

    def free(self, pages: List[int]) -> None:
        """Release one reference on every page in ``pages``.

        Validates the whole list BEFORE mutating — including rejecting
        duplicate ids within the call, which is what makes the
        validate-then-mutate order safe (see class docstring).
        """
        seen = set()
        for p in pages:
            if not (0 <= p < self.n_pages):
                raise ValueError(f"page {p} out of range [0, {self.n_pages})")
            if p in seen:
                raise ValueError(f"duplicate page {p} in free()")
            seen.add(p)
            if self._ref[p] < 1:
                raise ValueError(f"double free of page {p}")
        for p in pages:
            self._ref[p] -= 1
            if self._ref[p] == 0:
                self._free.append(p)
        # Re-cache in REVERSE list order, so earlier-listed pages end up
        # more recently used and outlive later ones.  Callers list pages
        # in block-table (chain) order, and the chained prefix index
        # loses every page BELOW a missing link — a chain's deep tail is
        # always the cheaper eviction, its head the costlier one.
        for p in reversed(pages):
            if self._ref[p] == 0 and p in self._indexed:
                # last reader gone, contents indexed: warm-cache entry
                self._cached.pop(p, None)
                self._cached[p] = None  # most-recently-used position
        self._free.sort(reverse=True)  # deterministic reuse order
        self._enforce_budget()


@dataclasses.dataclass
class PageGrant:
    """One admitted request's page reservation (the reserve-hook currency).

    ``pages`` — the slot's block-table entries in logical order (length ==
    the request's page need).  The leading ``n_shared`` entries are
    READ-ONLY shared prefix pages (refcounted; possibly backing other
    slots too) — the engine never writes through them.  ``start`` — first
    prompt position the engine must still prefill (0 when nothing was
    shared; the matched prefix's K/V is already resident).  ``cow`` —
    optional ``(src, dst)`` physical pair: the engine must copy page
    ``src`` onto ``dst`` BEFORE any write lands in ``dst`` (the
    copy-on-write fork of the last prefix page, taken when the tail
    re-enters a matched page).  ``refs`` — every page id holding one of
    this grant's allocator references, freed together on release:
    ``pages`` plus the COW source, whose content must stay pinned at
    least until the fork copy has run.

    An EMPTY grant (``pages == []``) is a real admission — zero-page
    archs (mamba state, SWA rings: nothing paged) reserve nothing but
    still occupy a slot.  Exhaustion is signalled by ``reserve`` returning
    ``None``, never by emptiness.
    """

    pages: List[int]
    n_shared: int = 0
    start: int = 0
    cow: Optional[Tuple[int, int]] = None
    refs: Optional[List[int]] = None

    def __post_init__(self):
        if self.refs is None:
            self.refs = list(self.pages)


class PrefixIndex:
    """Content index of FULL prompt-prefix pages for cross-request sharing.

    A page's K/V depends on every token at or before it, so the key for
    page ``i`` is the ENTIRE token prefix it closes over —
    ``prompt[: (i + 1) * page_size]`` — not just the page's own tokens.
    ``match`` walks the longest chain of indexed full pages from the
    prompt's head; only pages fully covered by the prompt participate
    (a partial last page is never indexed: its storage still gets written
    by the owner's decode stream).

    Entries PERSIST after the owning request releases its pages: a
    refcount-0 page sits on the allocator free list with contents intact
    — a warm prefix cache.  Lifetime is now allocator-driven: the engine
    marks every registered page via ``PageAllocator.mark_indexed``, and
    the allocator's ``on_evict`` callback invokes :meth:`drop_pages`
    whenever a cached page is re-granted to a writer or swept by the
    cache budget — keys and storage die together, so a match can never
    alias rewritten storage.  Registration is deferred until the owner's
    K/V has actually landed on device (the engine registers post-scatter
    / post-last-chunk for prompts and at slot release for decode-filled
    pages), so a match never reads pages that are still being computed.

    Host-side bookkeeping only.  Keys are CHAINED digests — page ``i``'s
    key hashes page ``i - 1``'s key together with page ``i``'s own token
    bytes — so a key still commits to the entire prefix while
    registration/matching stay O(pages) in time and memory (materializing
    ``prompt[:(i + 1) * page_size]`` per page would be quadratic: ~130 MB
    of key bytes for a 32k prompt at 64-token pages).  One key maps to at
    most one page and vice versa.
    """

    def __init__(self, page_size: int):
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        self.page_size = page_size
        # engine-thread-confined, like PageAllocator: lookups and inserts
        # happen only from admission/release paths on the owning engine
        self._by_key: dict = {}
        self._by_page: dict = {}
        # chain linkage (parent key -> child keys) for subtree drops:
        # sessions extend a prefix, so closing one is a branch delete
        self._children: dict = {}

    def __len__(self) -> int:
        return len(self._by_key)

    def _page_keys(self, prompt: np.ndarray) -> Iterator[bytes]:
        """Chained per-full-page keys; each commits to the whole prefix."""
        arr = np.ascontiguousarray(np.asarray(prompt, np.int32))
        P = self.page_size
        key = b""
        for i in range(len(arr) // P):
            key = hashlib.blake2b(
                key + arr[i * P : (i + 1) * P].tobytes(), digest_size=16
            ).digest()
            yield key

    def register(self, prompt: np.ndarray, pages) -> List[int]:
        """Index every FULL page of ``prompt`` backed by ``pages``.

        ``pages[i]`` must be the physical page holding positions
        ``[i * page_size, (i + 1) * page_size)`` (the slot's block-table
        row works verbatim).  First registration wins: an existing entry
        for the same key is kept — its page already holds identical
        content, and churning entries would invalidate live matches for
        no gain.  Returns the physical pages NOW backing the chain (the
        kept page where an entry already existed) so the caller can hand
        exactly those to ``PageAllocator.mark_indexed``.
        """
        backing: List[int] = []
        prev = b""
        for i, key in enumerate(self._page_keys(prompt)):
            # linkage is key-derived (prev + tokens), so recording it even
            # for kept entries is idempotent and keeps branches walkable
            self._children.setdefault(prev, set()).add(key)
            prev = key
            page = self._by_key.get(key)
            if page is not None:  # first registration won; same content
                backing.append(page)
                continue
            page = int(pages[i])
            old = self._by_page.pop(page, None)
            if old is not None:  # page re-registered under new content
                del self._by_key[old]
            self._by_key[key] = page
            self._by_page[page] = key
            backing.append(page)
        return backing

    def match(self, prompt: np.ndarray) -> List[int]:
        """Longest chain of indexed full-prefix pages for ``prompt``."""
        chain: List[int] = []
        for key in self._page_keys(prompt):
            page = self._by_key.get(key)
            if page is None:
                break
            chain.append(page)
        return chain

    def drop_pages(self, pages) -> None:
        """Forget entries whose physical page is being re-granted to a writer."""
        for p in pages:
            key = self._by_page.pop(int(p), None)
            if key is not None:
                del self._by_key[key]
                self._children.pop(key, None)

    def drop_branch(self, prompt: np.ndarray) -> List[int]:
        """Forget the prompt's full-page chain AND every registered
        extension of it (session close: the conversation's own pages plus
        all replies/turns built on top).  Returns the physical pages whose
        entries died, so the owner can release them from the allocator's
        warm cache in the same operation.

        Callers pass the SESSION's prompt, not a shared system prefix —
        pages keyed at or below the given prefix die for every session
        that shared them (correctness is unaffected: they re-prefill on
        next use).  If an interior page was already evicted, the chain
        walk stops there; the now-unreachable deeper entries decay through
        the allocator's LRU instead.
        """
        chain: List[bytes] = []
        for key in self._page_keys(prompt):
            if key not in self._by_key:
                break
            chain.append(key)
        if not chain:
            return []
        kill = list(chain)
        stack = [chain[-1]]
        while stack:
            for child in self._children.get(stack.pop(), ()):
                if child in self._by_key:  # linkage may outlive evictions
                    kill.append(child)
                    stack.append(child)
        dropped: List[int] = []
        for key in kill:
            page = self._by_key.pop(key, None)
            if page is not None:
                del self._by_page[page]
                dropped.append(page)
            self._children.pop(key, None)
        return dropped

    def clear(self) -> None:
        self._by_key.clear()
        self._by_page.clear()
        self._children.clear()


@dataclasses.dataclass(frozen=True)
class RejectedOverload:
    """Structured shed record attached to a request the admission policy
    dropped instead of admitting — the overload contract is an explicit
    rejection the client can retry against, never silent starvation.

    ``reason`` — why it was shed (``"deadline-expired"``, ``"shutdown"``).
    ``waited_ms`` — how long the request sat in the queue before shedding.
    ``queue_depth`` — waiters (including this one) at the shed decision.
    ``deadline_ms`` — the request's own admission deadline, if it had one.
    """

    uid: int
    reason: str
    waited_ms: float
    queue_depth: int
    deadline_ms: Optional[float] = None


@dataclasses.dataclass
class AdmissionPolicy:
    """Overload-aware admission: degrade rank tier under pressure, shed
    deadline-expired waiters.

    Tier semantics: tier 0 is the full serving rank; higher indices are
    NESTED cheaper ranks (prefix slices of the same factors — see
    ``core.lowrank.slice_rank``).  Under pressure a new admission is
    degraded to the deepest tier its ``min_tier`` allows instead of
    queueing behind work the pool cannot hold — quality sheds before
    latency does, and every degraded response carries the tier's
    spectral-bound certificate so the delta is reported, not silent.

    Pressure is EITHER signal: queue depth at/above
    ``degrade_queue_depth`` waiters, or the free-page fraction below
    ``degrade_free_frac``.  ``None`` disables a signal; with both None
    (the default) tiers are only ever what the request pinned itself.
    ``shed_deadlines`` — drop waiters whose ``deadline_ms`` expired
    before admission, with a :class:`RejectedOverload` attached.
    """

    n_tiers: int = 1
    degrade_queue_depth: Optional[int] = None
    degrade_free_frac: Optional[float] = None
    shed_deadlines: bool = True

    def choose_tier(self, request, queue_depth: int, free_frac: float) -> int:
        base = int(getattr(request, "tier", 0))
        if self.n_tiers <= 1:
            return base
        if getattr(request, "_parent", None) is not None:
            # a preempted request resumes at the tier it started on — its
            # registered K/V bytes and its emitted tokens are tier-specific,
            # and mid-request degradation would break bit-exact resume
            return base
        pressured = (
            self.degrade_queue_depth is not None
            and queue_depth >= self.degrade_queue_depth
        ) or (
            self.degrade_free_frac is not None and free_frac < self.degrade_free_frac
        )
        if not pressured:
            return base
        cap = min(int(getattr(request, "min_tier", 0)), self.n_tiers - 1)
        return max(base, cap)


@dataclasses.dataclass
class RoutingPolicy:
    """Load-aware replica routing for the cluster front door.

    ``pick`` receives one ``(replica_id, queue_depth, pages_used)`` triple
    per HEALTHY replica and returns the replica id to route the next
    request to: least queue depth first (waiters dominate TTFT), then
    least pages used (KV footprint approximates outstanding decode work),
    then lowest id — a total order, so routing is deterministic for a
    deterministic trace.
    """

    def pick(self, loads: List[Tuple[int, int, int]]) -> int:
        if not loads:
            raise ValueError("no healthy replicas to route to")
        return min(loads, key=lambda t: (t[1], t[2], t[0]))[0]


@dataclasses.dataclass
class FailoverBudget:
    """Per-request failover accounting for the cluster.

    A request whose replica dies is re-enqueued at most ``max_failovers``
    times; each re-enqueue is delayed by capped exponential backoff with
    deterministic jitter (same formula as ``runtime.fault_tolerance``,
    duplicated here because the scheduler layer stays jax-import-free):
    attempt ``k`` waits ``min(base_ms * 2**k, cap_ms)`` scaled by a factor
    in [0.5, 1.0] hashed from ``(salt, k)`` — typically salted with the
    request uid so concurrent failovers of different requests spread out
    instead of thundering back in lockstep.
    """

    max_failovers: int = 2
    base_ms: float = 0.0
    cap_ms: float = 250.0

    def backoff_ms(self, attempt: int, salt: int = 0) -> float:
        if self.base_ms <= 0:
            return 0.0
        raw = min(self.base_ms * (2.0 ** max(attempt, 0)), self.cap_ms)
        h = hashlib.blake2b(f"{salt}:{attempt}".encode(), digest_size=8).digest()
        frac = 0.5 + (int.from_bytes(h, "big") / 2.0**64) * 0.5
        return raw * frac


class Scheduler:
    """FIFO admission control on top of a :class:`SlotAllocator`.

    ``enqueue`` never blocks; ``admit`` drains the queue into free slots and
    returns the (slot, request) placements made this round.

    Paged engines additionally pass ``reserve``/``release_grant`` hooks:
    ``reserve(req)`` returns an opaque grant (:class:`PageGrant` in
    practice — possibly EMPTY for zero-page archs) or ``None`` on
    exhaustion; the grant lands in ``slot_pages[slot]`` and is handed back
    to ``release_grant`` when the slot frees.  Hook-shaped reservation is
    what lets admission do prefix matching + copy-on-write page
    reservation atomically while this class stays resource-agnostic.

    Exhaustion is detected with ``is None`` EXCLUSIVELY — an empty grant
    (``[]`` / ``PageGrant(pages=[])``) admits normally (zero-page archs).

    An optional :class:`AdmissionPolicy` adds the overload layer on top
    of plain FIFO: before each admission round, deadline-expired waiters
    are SHED (popped with a :class:`RejectedOverload` attached, collected
    via :meth:`drain_shed`), and each head-of-queue request is assigned
    its serving TIER from the policy's pressure signals before ``reserve``
    sees it.  ``pressure`` is a callable returning the free-resource
    fraction in [0, 1] (the engine passes its page-pool headroom); with no
    policy the scheduler behaves exactly as before — queue forever, tier
    untouched.
    """

    def __init__(
        self,
        allocator: SlotAllocator,
        *,
        reserve: Optional[Callable[[object], Optional[object]]] = None,
        release_grant: Optional[Callable[[object], None]] = None,
        policy: Optional[AdmissionPolicy] = None,
        pressure: Optional[Callable[[], float]] = None,
    ):
        if (reserve is None) != (release_grant is None):
            raise ValueError("reserve and release_grant come together")
        self.allocator = allocator
        self.reserve = reserve
        self.release_grant = release_grant
        self.policy = policy
        self.pressure = pressure
        # engine-thread-confined (admission state mutated only from the
        # owning engine's step loop).  `len(queue)` is additionally polled
        # lock-free by the cluster router via Engine.n_waiting — a
        # single-reader load estimate, see the note on that property.
        self.slot_pages: dict = {}
        self.queue: Deque = collections.deque()
        self.shed: List = []
        self.degraded = 0  # admissions the policy moved to a cheaper tier
        # optional structured-event sink: on_event(kind, fields_dict).
        # Installed by Engine/Cluster when an event log is configured;
        # must never raise (post-mortem plumbing, not control flow).
        self.on_event: Optional[Callable[[str, dict], None]] = None

    @property
    def n_waiting(self) -> int:
        return len(self.queue)

    def enqueue(self, request) -> None:
        self.queue.append(request)

    def drain_shed(self) -> List:
        """Hand back (and clear) the requests shed since the last drain."""
        out, self.shed = self.shed, []
        return out

    def shed_request(self, request, reason: str) -> None:
        """Mark one waiter shed with a structured rejection (already popped)."""
        now = time.perf_counter()
        request.status = "shed"
        request.t_done = now
        request.rejected = RejectedOverload(
            uid=request.uid,
            reason=reason,
            waited_ms=(now - request.t_submit) * 1e3,
            queue_depth=len(self.queue) + 1,
            deadline_ms=getattr(request, "deadline_ms", None),
        )
        self.shed.append(request)
        if self.on_event is not None:
            self.on_event(
                "shed",
                {
                    "uid": request.uid,
                    "reason": reason,
                    "waited_ms": round(request.rejected.waited_ms, 3),
                    "queue_depth": request.rejected.queue_depth,
                },
            )

    def _shed_expired(self) -> None:
        now = time.perf_counter()
        kept: Deque = collections.deque()
        while self.queue:
            req = self.queue.popleft()
            dl = getattr(req, "deadline_ms", None)
            if getattr(req, "_parent", None) is not None:
                # preempted continuations are exempt: the deadline governs
                # ADMISSION latency, and this request already emitted its
                # first token before being preempted — shedding it now
                # would silently discard delivered work
                dl = None
            if dl is not None and (now - req.t_submit) * 1e3 > dl:
                self.shed_request(req, "deadline-expired")
            else:
                kept.append(req)
        self.queue = kept

    def admit(self) -> List[Tuple[int, object]]:
        """Place waiting requests in free slots, FIFO; returns this round's
        (slot, request) placements, each request's ``t_admit`` stamped."""
        if self.policy is not None and self.policy.shed_deadlines:
            self._shed_expired()
        placed = []
        while self.queue and self.allocator.n_free:
            req = self.queue[0]
            if self.policy is not None:
                free_frac = self.pressure() if self.pressure is not None else 1.0
                tier = self.policy.choose_tier(req, len(self.queue), free_frac)
                if tier > getattr(req, "tier", 0):
                    self.degraded += 1
                    req.tier = tier
                    if self.on_event is not None:
                        self.on_event(
                            "degrade",
                            {"uid": req.uid, "tier": tier,
                             "queue_depth": len(self.queue), "free_frac": round(free_frac, 4)},
                        )
            if self.reserve is not None:
                grant = self.reserve(req)
                if grant is None:  # page exhaustion queues; strict FIFO
                    break
                slot = self.allocator.alloc()
                self.slot_pages[slot] = grant
            else:
                slot = self.allocator.alloc()
            req = self.queue.popleft()
            if getattr(req, "t_admit", None) == 0.0:
                # set once: a request re-admitted elsewhere after a
                # failover keeps the time it first got a slot
                req.t_admit = time.perf_counter()
            placed.append((slot, req))
        return placed

    def release(self, slot: int) -> None:
        if self.reserve is not None:
            self.release_grant(self.slot_pages.pop(slot))
        self.allocator.free(slot)
