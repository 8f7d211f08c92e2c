"""Continuous-batching serving engine over a slotted or PAGED KV-cache pool.

The engine owns ONE batched decode cache of ``n_slots`` rows (the pool) and
runs an admit -> prefill -> fused-decode loop.  With ``page_size`` set, the
per-token cache leaves instead live in a shared pool of fixed-size PAGES
indexed through per-slot block tables: admission is gated on each request's
actual page need rather than an ``n_slots x max_len`` worst-case
reservation (so equal KV bytes admit strictly more concurrent requests),
decode attention goes through the block-table kernel path
("paged_decode_attention" in runtime/dispatch.py), and — with
``prefill_chunk`` — long prompts prefill in page-aligned chunks interleaved
with decode blocks, bounding the TTFT impact a long prefill has on running
requests.  The flat loop:

  * requests (prompt tokens, max_new_tokens, sampling params) enter a FIFO
    queue (:mod:`repro.serving.scheduler`) and are assigned cache slots as
    slots free up — slot exhaustion queues, it never crashes;
  * admitted requests are prefilled in right-padded micro-batches (causal
    masking keeps padded prefill exact for attention families; recurrent
    families group by exact length because SSM state integrates every input
    token) and their caches are scattered into the pool rows;
  * ALL active slots then share a DEVICE-RESIDENT fused decode block: a
    ``lax.scan`` runs ``decode_block`` tokens per host round-trip — decode
    step, per-slot sampling (:func:`sample_tokens`), stop-token/max-token
    detection, and position/token-buffer updates all on device.  The host
    syncs ONCE per block to drain the emitted (tokens, mask) stack, finish
    completed requests, and admit waiting ones;
  * finished sequences free their slot and the oldest waiting request is
    admitted at the next block boundary — the decode batch stays full under
    load.

Device-residency contract: the KV-cache pool and the per-slot token /
position / activity buffers are DONATED through the fused step (the jit
aliases them in place — no per-step cache copy is ever materialized), and
the cache never leaves the device.  Per-slot stop detection freezes a slot
the moment it emits its last token: a frozen slot keeps re-feeding its last
(token, position) pair, which makes its cache writes idempotent, while its
emit mask excludes everything after the stop from the drained results.

Kernel backend selection goes through the unified dispatch runtime:
every prefill/fused-decode trace happens inside ``use_dispatch``, so
``--kernels`` applies per engine step exactly as it does to the static
path; on TPU the decode step's attention lowers to the Pallas flash-decode
kernel (kernels/decode_attention.py).

Greedy determinism contract: with temperature 0 the engine emits, per
request, bit-identical tokens to ``serve_step.greedy_generate`` run on that
prompt alone (tests/test_engine_parity.py) — the scheduler and the fused
block change WHEN a sequence advances, never WHAT it computes.

Prefix sharing (``share_prefix=True``, paged mode): admission matches a new
request's prompt against a host-side :class:`PrefixIndex` of full
prompt-prefix pages — live ones (still referenced by another slot) and
cached ones (released but not yet re-granted: refcount 0, contents intact
on the free list).  Matched pages are mapped into the slot's block table
as READ-ONLY shared entries (one allocator reference each, counted once in
``pages_in_use``); only the unshared tail is allocated and prefilled,
entering the model MID-PROMPT through the chunked-prefill program at the
first unshared position.  When the tail would re-enter a matched page (the
whole prompt is covered: the last prompt token must still run to produce
the sampling logits), that page is COW-FORKED — copied onto a private page
— so a writer never mutates shared storage.  Because both the block-table
Pallas kernel and the gather-einsum oracle index physical pages
indirectly, aliased page ids need zero kernel changes, and greedy outputs
stay bit-identical to the unshared paged run: sharing relocates bytes,
never changes what is attended.

Profiler spans: each step and its phases open a host-side
``jax.profiler.TraceAnnotation``, never inside a jitted program.  With no
profiler session a span costs about a microsecond; under
``jax.profiler.trace`` the spans land on the host plane on the same clock
as the device's program executions, so device idle time can be put down to
what the host was doing.  ``engine.step`` (stat ``step``) contains:

  * ``engine.admit`` — scheduler admission, preemption placements, shedding;
  * ``engine.cow_fork`` — one copy-on-write page fork;
  * ``engine.prefill_group`` (stats ``G``, ``P``, ``uids``) — one grouped
    prefill, containing ``engine.page_scatter`` (its K/V written into the
    page pool) and ``engine.sample`` (its first tokens);
  * ``engine.chunk`` (stats ``uid``, ``start``, ``n``) — one prefill chunk;
  * ``engine.decode_block`` — one fused decode block from launch to the
    end of its drain, containing ``engine.decode_sync``, where the host
    waits for the block's outputs;
  * ``engine.finish`` — one request finishing or quarantined, containing
    ``engine.rematerialize``.

A span that one request caused carries that request's ``uid``.  With the
request's ``t_submit``, ``t_admit``, ``t_first`` and ``t_done`` this splits
its life into queue, prefill and decode.
"""

from __future__ import annotations

import dataclasses
import math
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.bounds import CompressionCertificate, certify_tier
from repro.core.lowrank import is_lowrank, slice_rank
from repro.runtime.dispatch import DispatchConfig, use_dispatch
from repro.runtime.fault_tolerance import FaultInjector, StepWatchdog
from repro.serving.sampling import (
    SALT_MULT,
    SamplingParams,
    sample_tokens,
    token_salts,
)
from repro.serving.scheduler import (
    AdmissionPolicy,
    PageAllocator,
    PageGrant,
    PrefixIndex,
    RejectedOverload,
    Scheduler,
    SlotAllocator,
)

__all__ = [
    "Request",
    "Engine",
    "SamplingParams",
    "AdmissionPolicy",
    "RejectedOverload",
    "FaultInjector",
    "percentile",
]


def percentile(sorted_vals, frac: float):
    """Nearest-rank percentile of an ascending-sorted sequence.

    The ONE latency-percentile definition shared by the launcher and the
    serving benchmark, so their reported p50/p95 agree on identical data.
    """
    n = len(sorted_vals)
    if n == 0:
        raise ValueError("percentile of empty sequence")
    return sorted_vals[max(0, math.ceil(frac * n) - 1)]

# Families whose decode state integrates every prefill token (recurrent /
# convolutional state): right-padding would corrupt the carried state, so
# admission micro-batches group these by EXACT prompt length.
_EXACT_LEN_FAMILIES = ("ssm", "hybrid")

# eos sentinel for the fused stop check when no eos token is configured:
# sampled token ids are always >= 0, so -1 never matches.
_NO_EOS = -1


@dataclasses.dataclass
class Request:
    """One generation request plus its per-request results/latency record."""

    prompt: np.ndarray  # (S,) int32 prompt tokens
    max_new_tokens: int
    sampling: SamplingParams = SamplingParams()
    extras: Dict[str, np.ndarray] = dataclasses.field(default_factory=dict)
    # overload / QoS contract (all optional; defaults reproduce plain FIFO):
    deadline_ms: Optional[float] = None  # shed if not admitted within this
    min_tier: int = 0  # deepest rank tier the client accepts under pressure
    tier: int = 0  # tier actually served (admission may raise, never lower)
    priority: int = 0  # higher-priority waiters may preempt lower actives
    # filled in by the engine:
    uid: int = -1
    tokens: List[int] = dataclasses.field(default_factory=list)
    t_submit: float = 0.0
    t_admit: float = 0.0  # placed in a slot; kept across preemption
    t_first: float = 0.0
    t_done: float = 0.0
    prefill_skipped: int = 0  # prompt tokens covered by shared prefix pages
    status: str = "ok"  # "ok" | "shed" | "error"
    rejected: Optional[RejectedOverload] = None  # set when status == "shed"
    error: Optional[str] = None  # set when status == "error"
    certificate: Optional[CompressionCertificate] = None  # served tier's bound
    # preemption internals: a resumed continuation points at the original
    # request, whose token stream it extends (never set by callers)
    _parent: Optional["Request"] = dataclasses.field(default=None, repr=False)

    def __post_init__(self):
        self.prompt = np.asarray(self.prompt, np.int32).reshape(-1)
        if self.prompt.size < 1:
            raise ValueError("empty prompt")
        if self.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")

    @property
    def done(self) -> bool:
        return len(self.tokens) >= self.max_new_tokens

    @property
    def latency(self) -> Optional[float]:
        """Submit-to-completion seconds, or ``None`` before completion.

        The timestamps default to 0.0, so subtracting them blindly would
        yield a huge NEGATIVE number (−t_submit) for an in-flight request
        — garbage that sorts, averages and compares without error.  The
        ``None`` forces callers to handle incomplete requests explicitly.
        """
        if self.t_done == 0.0 or self.t_submit == 0.0:
            return None
        return self.t_done - self.t_submit

    @property
    def ttft(self) -> Optional[float]:
        """Submit-to-first-token seconds, or ``None`` before the first emit."""
        if self.t_first == 0.0 or self.t_submit == 0.0:
            return None
        return self.t_first - self.t_submit

    def _salt(self, token_index: int) -> int:
        return (self.sampling.seed * SALT_MULT + token_index) & 0x7FFFFFFF


def _cache_batch_axis(leaf) -> int:
    # Pool-cache layout convention (serve_step.cache_specs): the slot/batch
    # dim is axis 1 on every stacked leaf, except the 6-D VLM self-KV
    # (G, n_self, B, S, KV, hd) where it is axis 2.
    return 2 if leaf.ndim == 6 else 1


def _scatter_slot_leaf(pl, pr, idx, n_slots: int):
    """Write micro-batch rows of ONE slot-resident leaf into pool rows."""
    ax = _cache_batch_axis(pl)
    if pl.shape[ax] != n_slots:  # fail loudly if the layout rule drifts
        raise ValueError(
            f"cache leaf {pl.shape} does not carry the slot dim "
            f"({n_slots}) on axis {ax}; _cache_batch_axis out of date?"
        )
    rows = jnp.moveaxis(pr, ax, 0)[: idx.shape[0]]
    merged = jnp.moveaxis(pl, ax, 0).at[idx].set(rows)
    return jnp.moveaxis(merged, 0, ax)


def _scatter_slots(pool, part, slots, n_slots: int):
    """Write micro-batch cache rows into pool rows ``slots`` (leaf-wise).

    ``part`` may carry MORE rows than ``slots`` (batch-bucketed prefill pads
    with dummy rows); only the first ``len(slots)`` rows are written.
    """
    idx = jnp.asarray(slots, jnp.int32)
    return jax.tree_util.tree_map(
        lambda pl, pr: _scatter_slot_leaf(pl, pr, idx, n_slots), pool, part
    )


def _scatter_page_leaf(pl, pr, bt_rows, page: int):
    """Write micro-batch rows of ONE paged leaf into its page pool.

    pr: the flat prefill leaf — slot-batch at ``ax``, sequence (padded to
    max_len by the model) at ``ax + 1``; pl: the pool with (P_phys, page)
    at the same axes.  Row g's sequence is cut into page-sized runs and
    scattered to the page ids in ``bt_rows[g]`` — allocated pages for the
    admitted request, the trash page for dummy rows and the unallocated
    tail (collisions on trash are harmless; it is never read validly).
    Every allocated page gets fully overwritten (the model zero-pads prompt
    KV to max_len), so slot reuse can never leak a previous occupant's
    cache through recycled pages.
    """
    ax = _cache_batch_axis(pl)
    G, S = pr.shape[ax], pr.shape[ax + 1]
    n_chunk = -(-S // page)
    pr2 = jnp.moveaxis(pr, (ax, ax + 1), (0, 1))  # (G, S, rest...)
    if n_chunk * page != S:
        pad = [(0, n_chunk * page - S)] + [(0, 0)] * (pr2.ndim - 2)
        pr2 = jnp.pad(pr2, [(0, 0)] + pad)
    rows = pr2.reshape((G * n_chunk, page) + pr2.shape[2:])
    ids = bt_rows[:, :n_chunk].reshape(-1)
    pl2 = jnp.moveaxis(pl, (ax, ax + 1), (0, 1))  # (P_phys, page, rest...)
    merged = pl2.at[ids].set(rows)
    return jnp.moveaxis(merged, (0, 1), (ax, ax + 1))


def _scatter_mixed(pool, part, paged_mask, slots, n_slots, bt_rows, page):
    """Leaf-wise prefill scatter for a paged cache: page-pool leaves go
    through their block-table rows, slot-resident leaves (mamba state, SWA
    rings, cross-KV) through the classic row scatter."""
    idx = jnp.asarray(slots, jnp.int32)

    def leaf(pl, pr, is_paged):
        if is_paged:
            return _scatter_page_leaf(pl, pr, bt_rows, page)
        return _scatter_slot_leaf(pl, pr, idx, n_slots)

    return jax.tree_util.tree_map(leaf, pool, part, paged_mask)


def _next_pow2(n: int, floor: int) -> int:
    v = max(floor, 1)
    while v < n:
        v *= 2
    return v


def _seed32(seed: int) -> int:
    """Fold an arbitrary Python-int seed into signed int32 (low 32 bits).

    The fused loop computes salts with wrapping int32 arithmetic; keeping
    the low 32 bits preserves the low 31 salt bits the host path masks to
    (see sampling.SALT_MULT), so streams agree for any seed magnitude.
    """
    v = seed & 0xFFFFFFFF
    return v - 0x100000000 if v >= 0x80000000 else v


class Engine:
    """Continuous-batching engine binding (model, params) to a KV pool.

    ``decode_block``: decode tokens per host round-trip.  The fused step
    scans this many device decode iterations between host syncs; 1 recovers
    the classic token-at-a-time loop (useful for debugging), the default 8
    amortizes host dispatch/transfer to <= 1 sync per 8 decoded tokens per
    slot.

    ``page_size`` switches the pool to PAGED mode: per-token cache leaves
    live in a shared pool of ``kv_pages`` fixed-size pages (plus one trash
    page) indexed through per-slot block tables, and admission is gated on
    a request's ACTUAL page need (``ceil((prompt + max_new) / page_size)``,
    reserved up front so decode never strands) instead of an ``n_slots x
    max_len`` worst-case reservation — so at equal KV bytes the paged pool
    admits strictly more concurrent requests whenever real footprints are
    below worst case.  ``kv_pages`` defaults to flat-equivalent capacity
    (``n_slots * ceil(max_len / page_size)``); deployments lower it to
    bank the savings.  Greedy outputs are bit-identical to the flat engine
    (the block table only relocates bytes, never changes what is attended).

    ``prefill_chunk`` (paged mode, families without cross-chunk prefill
    state) additionally splits prompts longer than the chunk into fixed
    chunks processed ONE per engine step, interleaved with decode blocks —
    a long prompt's prefill no longer stalls running decodes for its whole
    length, bounding TTFT for short requests under long-prompt traffic.

    ``share_prefix`` (paged mode, chunk-capable families) turns on
    refcounted prompt-prefix sharing: requests whose prompts repeat an
    earlier prompt's leading full pages (the common-system-prompt traffic
    pattern) map those pages read-only instead of re-allocating and
    re-prefilling them, so equal KV bytes admit strictly more concurrent
    requests.  Inert (no behavior change) for families whose prefill
    cannot enter mid-prompt (ssm/hybrid/swa/vlm/audio).  See the module
    docstring for the matching / copy-on-write contract.

    SESSION reuse: when a shared-prefix slot finishes, its DECODE-FILLED
    full pages are registered too, keyed by the chained digest of prompt
    + generated tokens (minus the final token, whose K/V the fused loop
    does not guarantee to have written) — so a follow-up turn whose
    prompt extends the previous reply matches deep into the conversation
    and prefills only its new suffix.  Before registration the generated
    span is REMATERIALIZED through the chunk-prefill program (logits
    discarded): decode's single-query kernel and the prefill program
    round differently in the last bits, and indexed pages must hold
    bitwise the bytes a cold re-prefill would produce or a follow-up
    matching them can flip a greedy argmax vs an unshared run.
    ``warm_cache_pages`` caps how many refcount-0 pages stay matchable
    (LRU eviction inside the allocator); None keeps every released page
    matchable until a writer needs it.
    """

    def __init__(
        self,
        model,
        params,
        *,
        n_slots: int,
        max_len: int,
        dispatch: Optional[DispatchConfig] = None,
        eos_token: Optional[int] = None,
        decode_block: int = 8,
        page_size: Optional[int] = None,
        kv_pages: Optional[int] = None,
        prefill_chunk: Optional[int] = None,
        share_prefix: bool = False,
        warm_cache_pages: Optional[int] = None,
        tiers: Optional[Sequence[float]] = None,
        tier_q: int = 0,
        admission: Optional[AdmissionPolicy] = None,
        injector: Optional[FaultInjector] = None,
        preempt: bool = False,
        watchdog: Optional[StepWatchdog] = None,
        on_event: Optional[Callable[[str, dict], None]] = None,
    ):
        self.model, self.params = model, params
        self.cfg = model.cfg
        self.n_slots, self.max_len = n_slots, max_len
        self.eos_token = eos_token
        if decode_block < 1:
            raise ValueError(f"decode_block must be >= 1, got {decode_block}")
        self.decode_block = decode_block
        self._dcfg = dispatch if dispatch is not None else DispatchConfig.from_arch(self.cfg)

        # ---- elastic rank tiers (nested prefix slices of one checkpoint) --
        tiers = tuple(float(f) for f in tiers) if tiers else (1.0,)
        if tiers[0] != 1.0:
            raise ValueError(f"tiers[0] must be 1.0 (the serving rank), got {tiers}")
        if any(not 0.0 < f <= 1.0 for f in tiers) or any(
            a <= b for a, b in zip(tiers, tiers[1:])
        ):
            raise ValueError(f"tiers must be strictly decreasing in (0, 1]: {tiers}")
        if len(tiers) > 1 and self.cfg.family in _EXACT_LEN_FAMILIES:
            # recurrent state rows of frozen slots DRIFT during another
            # tier's fused pass (re-fed tokens integrate into the state),
            # so multi-tier decode would corrupt live recurrent slots —
            # attention K/V is rewritten before it is read, recurrent
            # state is not
            raise ValueError(
                f"multi-tier serving is not supported for the "
                f"{self.cfg.family} family (recurrent decode state)"
            )
        self.tiers = tiers
        # tier 0 aliases the stored params; every other tier is a trace-time
        # prefix slice — zero extra parameter memory, one jitted program per
        # tier (jit re-traces per sliced shape through the same callables)
        self._tier_params = [params] + [slice_rank(params, f) for f in tiers[1:]]
        self.tier_certificates = self._build_tier_certificates(tier_q)
        self.admission = admission
        self.injector = injector
        self.preempt = preempt
        # health instrumentation: the watchdog times every step() so a
        # stalled fused block flags instead of hanging run() silently; the
        # cluster reads .median/.durations as the heartbeat baseline.  A
        # plain attribute — the cluster may attach one post-construction.
        self.watchdog = watchdog
        # structured-event sink shared with the scheduler (see Cluster's
        # EventLog); None keeps the hot path branch-free in spirit — one
        # `is not None` check per event site.
        self.on_event = on_event

        self.paged = page_size is not None
        self.page_size = page_size
        if prefill_chunk is not None:
            if not self.paged:
                raise ValueError("prefill_chunk requires page_size (paged mode)")
            if prefill_chunk < 1:
                raise ValueError(f"prefill_chunk must be >= 1, got {prefill_chunk}")
        self.prefill_chunk = prefill_chunk
        if share_prefix and not self.paged:
            raise ValueError("share_prefix requires page_size (paged mode)")
        if warm_cache_pages is not None and not self.paged:
            raise ValueError("warm_cache_pages requires page_size (paged mode)")
        self.share_prefix = share_prefix
        self.warm_cache_pages = warm_cache_pages
        if self.paged:
            if page_size < 1:
                raise ValueError(f"page_size must be >= 1, got {page_size}")
            if model.init_cache_paged is None:
                raise ValueError(f"{self.cfg.family} model has no paged cache builder")
            self.max_pages = -(-max_len // page_size)
            self.kv_pages = kv_pages if kv_pages is not None else n_slots * self.max_pages
            with use_dispatch(self._dcfg):
                self.cache, self._paged_mask = model.init_cache_paged(
                    n_slots, max_len, page_size, self.kv_pages
                )
            self._has_pages = any(jax.tree_util.tree_leaves(self._paged_mask))
            self._trash = self.kv_pages  # trash page id (attention.trash_page)
            self._bt = np.full((n_slots, self.max_pages), self._trash, np.int32)
            self._bt_dirty = True
            # sharing needs (a) something actually paged to share and (b) a
            # mid-prompt prefill entry point (the chunk program) for the
            # unshared tail — otherwise the flag is inert, not an error, so
            # one launcher config can cover mixed arch fleets
            self._share = (
                share_prefix and self._has_pages and model.prefill_chunk is not None
            )
            # ONE index per tier: a page's K/V bytes depend on the params
            # that computed them, so the same tokens served at different
            # ranks must never alias pages across tiers
            self._prefix = (
                [PrefixIndex(page_size) for _ in self.tiers] if self._share else None
            )
            # one chunk shape for BOTH long-prompt chunking and shared-tail
            # prefill (two C values would compile two chunk programs)
            self._chunk_C = (
                prefill_chunk
                if prefill_chunk is not None
                else (page_size if self._share else None)
            )
            # the allocator owns warm-cache lifetime: its on_evict callback
            # is the ONLY place index keys are dropped outside an explicit
            # reset, so keys and storage can never disagree
            self.page_pool = PageAllocator(
                self.kv_pages,
                cache_budget=warm_cache_pages,
                on_evict=self._on_evict,
            )
            self.scheduler = Scheduler(
                SlotAllocator(n_slots),
                reserve=self._reserve,
                release_grant=self._release_grant,
                policy=admission,
                pressure=self._free_page_frac,
            )
        else:
            self.kv_pages = self.max_pages = 0
            self._paged_mask = None
            self._has_pages = False
            self._share = False
            self._prefix = None
            self._chunk_C = None
            self.page_pool = None
            self.scheduler = Scheduler(SlotAllocator(n_slots), policy=admission)
            with use_dispatch(self._dcfg):
                self.cache = model.init_cache(n_slots, max_len)
        self.scheduler.on_event = on_event
        # byte accounting: paged leaves are banked per PAGE, everything else
        # (slot-resident leaves, flat pools) is resident up front
        paged_leaves = (
            jax.tree_util.tree_leaves(self._paged_mask) if self.paged else []
        )
        cache_leaves = jax.tree_util.tree_leaves(
            {k: v for k, v in self.cache.items() if k != "block_table"}
        )
        self._bytes_per_page = sum(
            l.nbytes // l.shape[_cache_batch_axis(l)]
            for l, m in zip(cache_leaves, paged_leaves)
            if m
        ) if self.paged else 0
        self._bytes_resident = sum(l.nbytes for l in cache_leaves) - (
            self._bytes_per_page * (self.kv_pages + 1) if self.paged else 0
        )
        self.kv_bytes_capacity = sum(l.nbytes for l in cache_leaves)
        self._chunking: Dict[int, list] = {}  # slot -> [request, next_start, row]
        # the (1, C) chunk-prefill program, shared by chunked prefill and
        # _rematerialize; the cache is donated.  Profile readers match its
        # module name, ``jit__lambda`` (bench/harness/trace.py): rename
        # both together.
        self._chunk_jit = jax.jit(
            lambda p, c, t, bt, st, nr: model.prefill_chunk(p, c, t, bt, st, nr),
            donate_argnums=(1,),
        )
        self._cow_fn = None  # jitted COW page copy (built on first fork)

        def grouped_prefill(p, b, li):
            return model.prefill(p, b, max_len, last_index=li)

        # all-greedy fast path: skip the top-k/categorical machinery (two
        # (B,V) argsorts + B categorical draws) on the per-token hot path
        def first_token(logits):
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)

        # named functions, so their executions show in a profile as
        # ``jit_grouped_prefill`` and ``jit_first_token``
        self._prefill_jit = jax.jit(grouped_prefill)
        self._argmax_jit = jax.jit(first_token)
        self._base_key = jax.random.PRNGKey(0)
        self._fused_cache: Dict[bool, Any] = {}  # greedy? -> jitted block fn

        # per-slot host state (None = slot idle); the int/bool arrays are
        # MIRRORS of the device buffers the fused step owns — the host only
        # rewrites them at admission/finish boundaries, between blocks.
        self._reqs: List[Optional[Request]] = [None] * n_slots
        self._pos = np.zeros((n_slots,), np.int32)  # next write position
        self._tokens = np.zeros((n_slots, 1), np.int32)  # last emitted token
        self._active = np.zeros((n_slots,), bool)
        self._emitted = np.zeros((n_slots,), np.int32)  # == len(req.tokens)
        self._max_new = np.zeros((n_slots,), np.int32)
        self._seeds = np.zeros((n_slots,), np.int32)
        self._temps = np.zeros((n_slots,), np.float32)
        self._topks = np.zeros((n_slots,), np.int32)
        self._next_uid = 0
        # perf accounting; callers read them around a window
        self.steps = 0  # device decode steps executed
        self.host_syncs = 0  # fused-block host round-trips
        # paged decode attention, per layer and decode step, summed over
        # fused blocks: pages the kernel walks (each slot up to its last
        # live page) and pages a whole-table walk would take
        self.attn_pages_walked = 0
        self.attn_pages_offered = 0
        self.decoded_tokens = 0  # tokens emitted by decode (excl. prefill)
        self.peak_active = 0  # max concurrently admitted requests
        self.prefill_chunks = 0  # chunked-prefill chunks executed
        # prefix-sharing accounting: pages mapped read-only instead of
        # allocated+prefilled, COW forks taken, and admissions that matched
        self.shared_page_hits = 0
        self.cow_forks = 0
        self.shared_admissions = 0
        # prompt tokens admissions did NOT have to re-prefill because the
        # matched prefix's K/V was already resident (sum of grant.start)
        self.skipped_prefill_tokens = 0
        # overload/robustness accounting
        self.preemptions = 0  # slots preempted for higher-priority waiters
        self.quarantined = 0  # requests errored out on non-finite logits
        # shared lock-free: the cluster monitor polls this from another
        # thread (check_health straggler detection); single-writer (the
        # engine thread), monotonically increasing, so a stale read only
        # delays detection by one monitor pass — never corrupts it
        self.straggler_flags = 0  # watchdog-flagged slow steps
        self.exported = 0  # in-flight requests evicted via export_inflight
        self._step_idx = 0  # engine step() invocations (injector clock)

    def _free_page_frac(self) -> float:
        """Free-page fraction in [0, 1] — the admission policy's pressure
        signal (1.0 for flat/zero-page engines: no page pressure exists)."""
        if not self.paged or self.kv_pages == 0:
            return 1.0
        return self.page_pool.n_free / self.kv_pages

    def _build_tier_certificates(self, tier_q: int):
        """Per-tier Thm-3.2 certificates off the compressed LM head.

        The certified quantity is the softmax deviation the TIER introduces
        over the stored serving rank: the spectral norm of the factor tail
        each slice drops.  Head-less or uncompressed checkpoints get a
        zero-error certificate (slicing them is the identity).
        """
        if len(self.tiers) == 1:
            return [None]
        head = None

        def walk(node):
            nonlocal head
            if is_lowrank(node):
                a, b = node["a"], node["b"]
                # prefer the classifier head (projects to vocab, 2-D); else
                # keep the widest factor pair as the certified proxy layer
                if b.ndim == 2 and b.shape[-1] == self.cfg.vocab:
                    head = (a, b, True)
                elif head is None or (not head[2] and a.size > head[0].size):
                    head = (a, b, False)
                return
            if isinstance(node, dict):
                for v in node.values():
                    walk(v)
            elif isinstance(node, (list, tuple)):
                for v in node:
                    walk(v)

        walk(self.params)
        certs = []
        key = jax.random.PRNGKey(0)
        for f in self.tiers:
            if head is None:
                certs.append(
                    CompressionCertificate(0.0, 1.0, 0.0, rank=0, q=tier_q)
                )
                continue
            a, b, _ = head
            r = a.shape[-1]
            k = max(1, min(r, int(math.ceil(f * r))))
            certs.append(certify_tier(a, b, k, key, q=tier_q))
        return certs

    # ------------------------------------------------------------------ #
    # submission / introspection
    # ------------------------------------------------------------------ #
    def _page_need(self, request) -> int:
        """Pages a request must reserve: its WHOLE footprint (prompt plus
        max_new_tokens), taken at admission so decode can never run out of
        pages mid-stream (no preemption machinery needed)."""
        if not self._has_pages:
            return 0
        return -(-(int(request.prompt.size) + request.max_new_tokens) // self.page_size)

    def _reserve(self, request) -> Optional[PageGrant]:
        """All-or-nothing page reservation for one request (Scheduler hook).

        Matches the prompt's leading FULL pages against the prefix index,
        takes one allocator reference per hit (reviving cached pages off
        the free list), and allocates only the unshared remainder.  On
        allocation failure every acquired reference is rolled back, so
        admission stays atomic and strictly FIFO.  A shared page is
        counted ONCE in ``pages_in_use`` no matter how many slots map it
        (refcounts); zero-page archs get an EMPTY grant, which is a real
        admission — only ``None`` means exhaustion.
        """
        if self.injector is not None and self.injector.deny_reserve(self._step_idx):
            return None  # injected pool exhaustion: admission queues/sheds
        need = self._page_need(request)
        L = int(request.prompt.size)
        peak0 = self.page_pool.peak_used  # restored if this transaction fails
        acquired: List[int] = []
        # L >= 2 keeps the mid-prompt entry at start >= 1: a fully-matched
        # single-token prompt would otherwise degenerate to start == 0
        if self._share and L >= 2:
            for p in self._prefix[request.tier].match(request.prompt):
                if len(acquired) >= need or not self.page_pool.acquire(p):
                    break
                acquired.append(p)
        k = len(acquired)
        start = k * self.page_size if k else 0
        if k and start == L:
            # the whole prompt is covered by matched pages — but the last
            # prompt token must still run (its logits seed sampling), so
            # re-enter mid-page and COW-fork the page it re-writes
            start = L - 1
        fork = bool(k) and (start // self.page_size) < k
        fresh = self.page_pool.alloc(need - k + (1 if fork else 0))
        if fresh is None and fork:
            # The fork wants one page BEYOND the request's declared
            # footprint, but submit() only guarantees need <= kv_pages —
            # retrying the identical transaction could LIVELOCK (a full
            # pool never grows).  Degrade instead: un-share the boundary
            # page (its tail prefills like any unshared page) and retry
            # at exactly ``need``, which the pool can always eventually
            # satisfy.
            self.page_pool.free([acquired.pop()])
            k -= 1
            start = k * self.page_size
            fork = False
            fresh = self.page_pool.alloc(need - k)
        if fresh is None:
            if acquired:
                self.page_pool.free(acquired)
            # atomic: with every ref rolled back (including one the COW
            # degrade gave back above), restore the high-water mark any
            # revive raised — those pages never backed admitted work, and
            # the head-of-queue retry re-runs this every step.  A no-op
            # when nothing was revived.
            self.page_pool.rollback_peak(peak0)
            return None
        # fresh pages are about to be WRITTEN, but no index scrub is needed
        # here: the allocator only grants an index-backed page through its
        # eviction path, which already dropped the keys via _on_evict
        if fork:
            grant = PageGrant(
                pages=acquired[:-1] + [fresh[0]] + fresh[1:],
                n_shared=k - 1,
                start=start,
                cow=(acquired[-1], fresh[0]),
                refs=acquired + fresh,  # pin the COW source until release
            )
        else:
            grant = PageGrant(pages=acquired + fresh, n_shared=k, start=start)
        if k:
            self.shared_admissions += 1
            self.shared_page_hits += grant.n_shared
            self.skipped_prefill_tokens += grant.start
            request.prefill_skipped = grant.start
            # credit the matched pages' warm-cache value ONLY on a grant
            # that sticks — a starved head-of-queue retry acquires and
            # rolls back every step and must not inflate eviction scores
            self.page_pool.record_saved(acquired[: grant.n_shared])
        return grant

    def _on_evict(self, pages: List[int]) -> None:
        """PageAllocator eviction callback: a cached page is being handed
        to a writer (or swept by the cache budget), so its index keys must
        die in the same operation — no stale ``match`` hits."""
        if self._prefix is not None:
            for index in self._prefix:
                index.drop_pages(pages)

    def _release_grant(self, grant: PageGrant) -> None:
        """Drop one reference on every page the grant holds (Scheduler
        hook).  Shared pages survive until their LAST reader releases;
        an index-backed page hitting refcount 0 becomes a warm-cache
        entry inside the allocator (LRU-ordered, evicted via _on_evict
        when re-granted for writing or swept by the cache budget)."""
        if grant.refs:
            self.page_pool.free(grant.refs)

    def reset_prefix_cache(self) -> None:
        """Forget every prefix-index entry (warm-up boundary).

        Refcounts and live allocations are untouched — already-admitted
        slots keep their shared pages; only FUTURE admissions stop
        matching until new prompts re-register.  The allocator's cache
        bookkeeping is flushed in the same operation (without counting
        evictions: this is a policy reset, not cache pressure)."""
        if self._prefix is not None:
            for index in self._prefix:
                index.clear()
            self.page_pool.flush_cache()

    def submit(self, request: Request) -> Request:
        if request.prompt.size + request.max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt ({request.prompt.size}) + max_new_tokens "
                f"({request.max_new_tokens}) exceeds max_len ({self.max_len})"
            )
        if self.paged and self._page_need(request) > self.kv_pages:
            raise ValueError(
                f"request needs {self._page_need(request)} pages but the pool "
                f"holds {self.kv_pages} — it could never be admitted"
            )
        request.uid = self._next_uid
        self._next_uid += 1
        request.t_submit = time.perf_counter()
        self.scheduler.enqueue(request)
        return request

    # n_active / n_waiting / pages_in_use are polled lock-free by the
    # cluster's routing pass from the monitor thread while the engine
    # thread mutates the underlying scheduler state.  That is deliberate:
    # they are single-writer load ESTIMATES — a stale value can only
    # misroute one admission to a slightly busier replica, and taking the
    # engine's step-loop hot path through a lock to sharpen a heuristic
    # would invert the cost/benefit.  Correctness-bearing cluster state is
    # what the `# guarded by:` annotations in serving/cluster.py cover.
    @property
    def n_active(self) -> int:
        return self.scheduler.allocator.n_active

    @property
    def n_waiting(self) -> int:
        return self.scheduler.n_waiting

    @property
    def has_work(self) -> bool:
        return self.n_active > 0 or self.n_waiting > 0

    @property
    def batch_utilization(self) -> float:
        """Fraction of executed decode-step rows that emitted a real token."""
        return self.decoded_tokens / (self.steps * self.n_slots) if self.steps else 0.0

    @property
    def tokens_per_sync(self) -> float:
        """Decoded tokens amortized per host round-trip."""
        return self.decoded_tokens / self.host_syncs if self.host_syncs else 0.0

    @property
    def pages_in_use(self) -> int:
        """Distinct physical pages currently referenced — a page shared by
        several slots is counted ONCE (it occupies one page of HBM)."""
        return self.page_pool.n_used if self.paged else 0

    @property
    def prefix_evictions(self) -> int:
        """Warm-cache pages evicted (writer re-grant or budget sweep)."""
        return self.page_pool.evictions if self.paged else 0

    @property
    def prefix_cached_pages(self) -> int:
        """Refcount-0 pages currently matchable in the prefix index."""
        return self.page_pool.n_cached if self.paged else 0

    @property
    def peak_pages_in_use(self) -> int:
        """Allocator-owned high-water page count: raised inside every
        allocation-changing operation (admission alloc, prefix acquire,
        COW fork), so pages held across chunked-prefill-only steps — or
        across a ``reset_counters`` boundary — are always observed."""
        return self.page_pool.peak_used if self.paged else 0

    @property
    def kv_bytes_in_use(self) -> int:
        """ACTUAL cache bytes backing admitted work: allocated pages (plus
        slot-resident leaves) in paged mode; in flat mode the whole pool is
        committed up front, so in-use == capacity regardless of load.

        Scope: the PERSISTENT pool only.  Both engines additionally
        materialize a transient per-admission prefill cache (one
        (G, max_len) micro-batch, freed after the scatter) that this metric
        — and ``kv_bytes_peak`` — deliberately exclude; size real HBM
        headroom as pool + one prefill micro-batch.  Chunked prefill
        shrinks that transient for long prompts to a single (1, chunk)
        slice."""
        if not self.paged:
            return self.kv_bytes_capacity
        return self._bytes_resident + self._bytes_per_page * self.pages_in_use

    @property
    def kv_bytes_peak(self) -> int:
        """High-water cache bytes actually backing admitted work."""
        if not self.paged:
            return self.kv_bytes_capacity
        return self._bytes_resident + self._bytes_per_page * self.peak_pages_in_use

    def reset_counters(self):
        """Re-arm the perf/accounting counters (warm-up boundary).

        Peaks re-arm to CURRENT usage, not zero: allocations held across
        the boundary (a request mid-chunked-prefill, live slots) would
        otherwise peak unobserved if no later admission re-sampled them,
        under-reporting ``kv_bytes_peak``.
        """
        self.steps = self.host_syncs = self.decoded_tokens = 0
        self.attn_pages_walked = self.attn_pages_offered = 0
        self.prefill_chunks = 0
        self.shared_page_hits = self.cow_forks = self.shared_admissions = 0
        self.skipped_prefill_tokens = 0
        self.straggler_flags = 0
        self.exported = 0
        self.peak_active = self.scheduler.allocator.n_active
        if self.paged:
            self.page_pool.reset_peak()
            self.page_pool.evictions = 0

    # ------------------------------------------------------------------ #
    # admission + prefill
    # ------------------------------------------------------------------ #
    def _admission_groups(self, placed):
        """Split (slot, req) placements into prefill micro-batches.

        A micro-batch runs ONE prefill program with ONE params pytree, so
        placements split by TIER first (each tier's sliced factors are a
        distinct pytree), then by the family's shape constraints.
        """
        by_tier: Dict[int, list] = {}
        for slot, req in placed:
            by_tier.setdefault(req.tier, []).append((slot, req))
        groups = []
        for tier in sorted(by_tier):
            tier_placed = by_tier[tier]
            exact = self.cfg.family in _EXACT_LEN_FAMILIES
            if not exact and self.cfg.sliding_window is not None:
                # SWA ring layout rotates by the PADDED length once it
                # exceeds the window — shorter requests in the pad would
                # land in wrong ring slots, so group by exact length there.
                exact = (
                    max(req.prompt.size for _, req in tier_placed)
                    > self.cfg.sliding_window
                )
            if exact:
                by_len: Dict[int, list] = {}
                for slot, req in tier_placed:
                    by_len.setdefault(req.prompt.size, []).append((slot, req))
                groups.extend(by_len.values())
            else:
                groups.append(tier_placed)
        return groups

    def _prefill_shape(self, n_reqs: int, max_prompt: int):
        """Bucket the micro-batch shape so live traffic triggers a BOUNDED
        number of prefill compiles: batch rows up to the next power of two
        (capped at n_slots, dummy rows are discarded by the scatter), and —
        for attention families, where last_index makes right-padding exact —
        prompt length up to the next power of two (capped at max_len and at
        the sliding window, past which the ring layout forbids padding)."""
        G = min(_next_pow2(n_reqs, 1), self.n_slots)
        P = max_prompt
        if self.cfg.family not in _EXACT_LEN_FAMILIES:
            cap = self.max_len
            if self.cfg.sliding_window is not None:
                cap = min(cap, self.cfg.sliding_window)
            P = max(max_prompt, min(_next_pow2(max_prompt, 8), cap))
        return G, P

    def _prefill_group(self, group):
        slots = [slot for slot, _ in group]
        reqs = [req for _, req in group]
        lens = np.array([r.prompt.size for r in reqs], np.int32)
        G, P = self._prefill_shape(len(reqs), int(lens.max()))
        uids = " ".join(str(r.uid) for r in reqs)
        with TraceAnnotation("engine.prefill_group", G=G, P=P, uids=uids):
            toks = np.zeros((G, P), np.int32)
            for i, r in enumerate(reqs):
                toks[i, : r.prompt.size] = r.prompt
            last_index = np.zeros((G,), np.int32)
            last_index[: len(reqs)] = lens - 1
            batch = {"tokens": jnp.asarray(toks)}
            for name in reqs[0].extras:
                rows = [r.extras[name] for r in reqs]
                rows += [np.zeros_like(rows[0])] * (G - len(reqs))
                batch[name] = jnp.asarray(np.stack(rows))

            padded_reqs = reqs + [None] * (G - len(reqs))
            tier = reqs[0].tier  # _admission_groups splits by tier
            with use_dispatch(self._dcfg):
                logits, part = self._prefill_jit(
                    self._tier_params[tier], batch, jnp.asarray(last_index)
                )
                if self.paged:
                    # dummy rows (and each slot's unallocated table tail)
                    # scatter to the trash page; allocated pages are fully
                    # overwritten
                    bt_rows = np.full((G, self.max_pages), self._trash, np.int32)
                    bt_rows[: len(slots)] = self._bt[slots]
                    pools = {k: v for k, v in self.cache.items() if k != "block_table"}
                    with TraceAnnotation("engine.page_scatter"):
                        merged = _scatter_mixed(
                            pools, part, self._paged_mask, slots, self.n_slots,
                            jnp.asarray(bt_rows), self.page_size,
                        )
                    merged["block_table"] = self.cache["block_table"]
                    self.cache = merged
                    if self._share:
                        # registration is DEFERRED to here (not admission) so
                        # a match can never alias pages whose prefill has not
                        # landed on device yet — same-round admissions simply
                        # miss the sharing opportunity once
                        for slot, req in group:
                            backing = self._prefix[req.tier].register(
                                req.prompt, self._bt[slot]
                            )
                            self.page_pool.mark_indexed(backing)
                else:
                    self.cache = _scatter_slots(self.cache, part, slots, self.n_slots)
                with TraceAnnotation("engine.sample"):
                    first = self._sample(logits, padded_reqs, [0] * G)

        now = time.perf_counter()
        finished = []
        for i, (slot, req) in enumerate(group):
            self._activate_slot(slot, req, int(lens[i]), int(first[i]), now)
        for slot, _ in group:
            done = self._maybe_finish(slot)
            if done is not None:
                finished.append(done)
        return finished

    def _activate_slot(self, slot: int, req: Request, pos: int, first_tok: int, now: float):
        """Post-prefill bookkeeping shared by grouped and chunked prefill."""
        self._reqs[slot] = req
        self._pos[slot] = pos
        self._tokens[slot, 0] = first_tok
        self._active[slot] = True
        self._emitted[slot] = 1
        self._max_new[slot] = req.max_new_tokens
        self._seeds[slot] = _seed32(req.sampling.seed)
        self._temps[slot] = req.sampling.temperature
        self._topks[slot] = req.sampling.top_k
        req.certificate = self.tier_certificates[req.tier]
        req.t_first = now
        req.tokens.append(first_tok)

    # ------------------------------------------------------------------ #
    # sampling / completion
    # ------------------------------------------------------------------ #
    def _sample(self, logits, reqs, token_indices):
        """Sample one token per logits row for the given requests (prefill
        boundary; the decode hot path samples inside the fused block)."""
        if all(r is None or r.sampling.temperature == 0 for r in reqs):
            return np.asarray(self._argmax_jit(logits))
        B = logits.shape[0]
        temps = np.zeros((B,), np.float32)
        topks = np.zeros((B,), np.int32)
        salts = np.zeros((B,), np.int32)
        for i, (req, ti) in enumerate(zip(reqs, token_indices)):
            if req is None:
                continue
            temps[i] = req.sampling.temperature
            topks[i] = req.sampling.top_k
            salts[i] = req._salt(ti)
        out = sample_tokens(
            logits,
            self._base_key,
            jnp.asarray(salts),
            jnp.asarray(temps),
            jnp.asarray(topks),
        )
        return np.asarray(out)

    def _clear_slot(self, slot: int) -> None:
        """Reset one slot's host mirrors and hand it back to the scheduler
        (the shared tail of finish / preempt / quarantine)."""
        self._reqs[slot] = None
        self._pos[slot] = 0
        self._tokens[slot, 0] = 0
        self._active[slot] = False
        self._emitted[slot] = 0
        self._max_new[slot] = 0
        self._seeds[slot] = 0
        self._topks[slot] = 0
        self._temps[slot] = 0.0
        self.scheduler.release(slot)
        if self.paged:
            # Compact the table row back to all-trash BEFORE the next
            # device launch: the freed pages may be re-granted to another
            # slot, and a stale row would let this (now inactive) slot's
            # idempotent re-writes land in pages it no longer owns.
            self._bt[slot] = self._trash
            self._bt_dirty = True

    def _finalize(self, req: Request) -> Request:
        """Fold a finished CONTINUATION back into its original request.

        A preempted request's client holds the ORIGINAL object; the
        continuation's tokens extend its stream and its terminal state
        (timestamps, status) transfers, so callers never see the internal
        re-queue.  Non-continuations pass through untouched.
        """
        root = req._parent
        if root is None:
            return req
        root.tokens.extend(req.tokens)
        root.t_done = req.t_done
        root.status = req.status
        root.error = req.error
        return root

    def _maybe_finish(self, slot: int) -> Optional[Request]:
        req = self._reqs[slot]
        if req is None:
            return None
        hit_eos = self.eos_token is not None and req.tokens and req.tokens[-1] == self.eos_token
        if not (req.done or hit_eos):
            return None
        with TraceAnnotation("engine.finish", uid=req.uid):
            req.t_done = time.perf_counter()
            if self._share:
                # Register the DECODE-FILLED pages before the slot releases:
                # a follow-up turn whose prompt extends (prompt + reply)
                # matches them read-only and prefills only its new suffix.
                # The registered sequence stops one token short of the
                # reply — token k's K/V is written while producing token
                # k+1, so the LAST token's K/V is only (maybe) written by
                # frozen-slot re-feeds; likewise an EOS tail ends at the
                # EOS token itself, which is tokens[-1] and thus excluded.
                # register() only keys FULL pages, so the partial last
                # page is never offered.  Must precede release(): free()
                # can only turn these pages into warm-cache entries if
                # they are already marked as indexed.
                seq = np.concatenate(
                    [req.prompt, np.asarray(req.tokens[:-1], np.int32)]
                )
                full_end = (seq.size // self.page_size) * self.page_size
                if full_end > req.prompt.size:
                    self._rematerialize(
                        slot, seq, int(req.prompt.size), full_end, req.tier
                    )
                backing = self._prefix[req.tier].register(seq, self._bt[slot])
                self.page_pool.mark_indexed(backing)
            self._clear_slot(slot)
            return self._finalize(req)

    def _sync_block_table(self):
        """Push host block-table edits to the device cache (pre-launch)."""
        if self.paged and self._bt_dirty:
            self.cache["block_table"] = jnp.asarray(self._bt)
            self._bt_dirty = False

    def _cow_fork(self, src: int, dst: int, uid: int):
        """Copy physical page ``src`` onto ``dst`` in every paged leaf, for
        the admission of request ``uid``.

        The copy-on-write step of shared-prefix admission: the forked slot
        writes its last prompt token (and nothing else) into ``dst``, so
        the shared original is never mutated.  ``src``'s content is pinned
        by the grant's extra reference until release, so the copy can
        never race a re-grant.  One jitted program per engine (page ids
        are runtime data), pools donated — no pool copy materializes.
        """
        from repro.models.attention import copy_page

        if self._cow_fn is None:
            mask = self._paged_mask

            def cow(pools, s, d):
                return jax.tree_util.tree_map(
                    lambda pl, m: (
                        copy_page(pl, s, d, axis=_cache_batch_axis(pl)) if m else pl
                    ),
                    pools,
                    mask,
                )

            self._cow_fn = jax.jit(cow, donate_argnums=(0,))
        pools = {k: v for k, v in self.cache.items() if k != "block_table"}
        with TraceAnnotation("engine.cow_fork", uid=uid), use_dispatch(self._dcfg):
            pools = self._cow_fn(pools, jnp.int32(src), jnp.int32(dst))
        pools["block_table"] = self.cache["block_table"]
        self.cache = pools
        self.cow_forks += 1

    # ------------------------------------------------------------------ #
    # chunked prefill (paged mode): one chunk per engine step
    # ------------------------------------------------------------------ #
    def _chunk_step(self):
        """Run ONE prefill chunk for the oldest chunking request; returns
        ``(finished, n_real)`` — the requests completed by this chunk and
        how many REAL prompt tokens it processed (the step loop's budget
        currency).

        Chunks are a fixed (1, chunk) shape (the last chunk of a prompt is
        right-padded; ``n_real`` masks the tail), so live traffic compiles
        exactly one chunk program per arch.  The final chunk's logits
        sample the request's first token and the slot joins the decode
        batch at the next block.
        """
        slot = next(iter(self._chunking))  # dict preserves admission order
        req, start, row = self._chunking[slot]
        C = self._chunk_C
        plen = int(req.prompt.size)
        n = min(C, plen - start)
        with TraceAnnotation("engine.chunk", uid=req.uid, start=start, n=n):
            toks = np.zeros((1, C), np.int32)
            toks[0, :n] = req.prompt[start : start + n]
            with use_dispatch(self._dcfg):
                logits, self.cache = self._chunk_jit(
                    self._tier_params[req.tier],
                    self.cache,
                    jnp.asarray(toks),
                    jnp.asarray(row),
                    jnp.int32(start),
                    jnp.int32(n),
                )
            self.prefill_chunks += 1
            start += n
            if start < plen:
                self._chunking[slot][1] = start
                return [], n
            del self._chunking[slot]
            # last chunk landed: publish the row so the decode block (and its
            # page writes) see the slot's pages from here on
            self._bt[slot] = row
            self._bt_dirty = True
            if self._share:
                # the prompt's full pages are now completely written on
                # device: safe to offer them to future admissions
                backing = self._prefix[req.tier].register(req.prompt, row)
                self.page_pool.mark_indexed(backing)
            with TraceAnnotation("engine.sample"):
                first = self._sample(logits, [req], [0])
            self._activate_slot(slot, req, plen, int(first[0]), time.perf_counter())
        done = self._maybe_finish(slot)
        return ([done] if done is not None else []), n

    def _rematerialize(
        self, slot: int, seq: np.ndarray, start: int, end: int, tier: int = 0
    ):
        """Rewrite positions ``[start, end)`` of the slot's pages through
        the (1, C) chunk-prefill program, discarding the logits.

        Decode filled those K/V entries via the single-query decode path,
        whose floating-point reduction order differs from the prefill
        program's in the last bits.  Pages offered to the prefix index
        must hold bitwise the bytes a cold re-prefill of the same tokens
        would produce, or a follow-up turn that matches them can flip a
        greedy argmax relative to an unshared run.  Re-feeding the
        generated tokens through the canonical prefill program restores
        those bytes; the cost is O(reply length) at release, OFF any
        follow-up's TTFT path.  Only ``[start, end)`` needs rewriting:
        ``end`` is the last full-page boundary (partial tails are never
        indexed) and positions ``< start`` were prefill-written at
        admission.  Reuses the one compiled chunk program — no extra
        compilation, and pad positions past ``n_real`` write to the
        trash page, so nothing outside the slot's own pages is touched.
        """
        C = self._chunk_C
        row = self._bt[slot]
        uid = self._reqs[slot].uid
        with TraceAnnotation("engine.rematerialize", uid=uid, start=start, end=end):
            while start < end:
                n = min(C, end - start)
                toks = np.zeros((1, C), np.int32)
                toks[0, :n] = seq[start : start + n]
                with use_dispatch(self._dcfg):
                    _, self.cache = self._chunk_jit(
                        self._tier_params[tier],
                        self.cache,
                        jnp.asarray(toks),
                        jnp.asarray(row),
                        jnp.int32(start),
                        jnp.int32(n),
                    )
                self.prefill_chunks += 1
                start += n

    # ------------------------------------------------------------------ #
    # the fused decode block (device-resident inner loop)
    # ------------------------------------------------------------------ #
    def _fused_fn(self, greedy: bool):
        """Build (once per greedy/sampling variant) the jitted fused block.

        The block scans ``decode_block`` decode iterations on device.  Per
        iteration: decode_step -> sample -> per-slot stop detection ->
        position/token updates, with NO host involvement.  Frozen (finished
        or empty) slots re-feed their last (token, position) pair, so their
        attention-cache writes are idempotent; recurrent (SSM) state rows of
        frozen slots do drift, but a slot's state is fully overwritten by
        the prefill scatter before reuse, and rows are independent across
        the batch, so live slots never observe it.

        Donation: the cache pool and every per-slot buffer are donated —
        XLA aliases them in place, so the multi-GB pool is never copied per
        block, let alone per token.
        """
        fn = self._fused_cache.get(greedy)
        if fn is not None:
            return fn
        model = self.model
        n_steps = self.decode_block
        eos = _NO_EOS if self.eos_token is None else int(self.eos_token)

        def fused(params, cache, tokens, pos, active, emitted, max_new, seeds,
                  temps, topks, base_key, poison_slot, poison_rel):
            sids = jnp.arange(tokens.shape[0], dtype=jnp.int32)

            def body(carry, i):
                cache, tokens, pos, active, emitted, quar = carry
                logits, cache = model.decode_step(params, cache, tokens, pos)
                # fault injection rides two runtime scalars ((-1, -1) when
                # unarmed selects nothing) — the compiled program is
                # byte-identical armed or not, so injection tests exercise
                # exactly the production quarantine path
                hit = (sids == poison_slot) & (i == poison_rel)
                logits = jnp.where(hit[:, None], jnp.nan, logits)
                if greedy:
                    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                else:
                    # salt from the CURRENT emitted count == the host path's
                    # token_index (len(req.tokens) before this append)
                    nxt = sample_tokens(
                        logits, base_key, token_salts(seeds, emitted), temps, topks
                    )
                # QUARANTINE: a slot whose logits went non-finite freezes
                # THIS step — the garbage token is never emitted, never fed
                # back, and the rest of the batch keeps decoding.  The host
                # drain errors the request out after the block.
                bad = active & ~jnp.isfinite(logits).all(axis=-1)
                quar = quar | bad
                emit = active & ~bad
                # frozen slots re-feed their last token at their frozen
                # position (idempotent cache rewrite, masked out of emits)
                nxt = jnp.where(emit, nxt, tokens[:, 0])
                step = emit.astype(jnp.int32)
                pos = pos + step
                emitted = emitted + step
                active = emit & (emitted < max_new) & (nxt != eos)
                return (cache, nxt[:, None], pos, active, emitted, quar), (nxt, emit)

            carry, (toks, emits) = jax.lax.scan(
                body,
                (cache, tokens, pos, active, emitted, jnp.zeros_like(active)),
                jnp.arange(n_steps, dtype=jnp.int32),
            )
            cache, tokens, pos, active, emitted, quar = carry
            return cache, tokens, pos, active, emitted, quar, toks, emits

        fn = jax.jit(fused, donate_argnums=(1, 2, 3, 4, 5))
        self._fused_cache[greedy] = fn
        return fn

    # ------------------------------------------------------------------ #
    # the engine step
    # ------------------------------------------------------------------ #
    def step(self) -> List[Request]:
        """Admit waiting requests (paged mode: gated on free PAGES, with
        long prompts routed to the chunked-prefill queue), run at most one
        prefill chunk, then one fused decode block (up to ``decode_block``
        tokens per active slot with a single host round-trip); returns the
        requests that finished during this step.

        When a :class:`StepWatchdog` is attached, every step is timed and
        fed to it: a step slower than the watchdog's straggler threshold
        bumps ``straggler_flags`` and emits a ``"straggler"`` event — the
        health signal the cluster's heartbeat monitor consumes, instead of
        a stalled fused block hanging ``run()`` silently.
        """
        self._step_idx += 1
        with TraceAnnotation("engine.step", step=self._step_idx):
            if self.watchdog is None:
                return self._step_inner()
            t0 = time.monotonic()
            finished = self._step_inner()
            if self.watchdog.observe(self._step_idx, time.monotonic() - t0):
                self.straggler_flags += 1
                if self.on_event is not None:
                    self.on_event(
                        "straggler",
                        {"step": self._step_idx,
                         "seconds": round(self.watchdog.durations[-1], 6),
                         "median_s": round(self.watchdog.median, 6)},
                    )
            return finished

    def _step_inner(self) -> List[Request]:
        finished: List[Request] = []
        if self.injector is not None:
            self.injector.on_step(self._step_idx)

        with TraceAnnotation("engine.admit"):
            placed = self.scheduler.admit()
            if self.preempt:
                placed.extend(self._preempt_for_waiters())
            # deadline-expired waiters shed by admission surface as finished
            # requests with status "shed" and a structured rejection attached
            finished.extend(self.scheduler.drain_shed())
        if placed:
            # page peaks are tracked INSIDE the allocator at every
            # allocation-changing site; only the admitted-request peak is
            # engine-level state
            self.peak_active = max(self.peak_active, self.scheduler.allocator.n_active)

        chunking = (
            self.paged
            and self.prefill_chunk is not None
            and self.model.prefill_chunk is not None
        )
        direct = []
        for slot, req in placed:
            row = None
            grant = None
            if self.paged:
                grant = self.scheduler.slot_pages[slot]
                row = np.full((self.max_pages,), self._trash, np.int32)
                row[: len(grant.pages)] = grant.pages
            if grant is not None and grant.start > 0:
                # Shared-prefix admission: the matched pages' K/V is already
                # resident, so prefill SKIPS them entirely and enters the
                # model mid-prompt (grant.start) through the chunk program.
                # When the tail re-enters the last matched page (whole
                # prompt covered), COW-fork it first so the re-write of the
                # final prompt token never lands in shared storage.
                if grant.cow is not None:
                    self._cow_fork(*grant.cow, uid=req.uid)
                self._chunking[slot] = [req, grant.start, row]
            elif chunking and req.prompt.size > self.prefill_chunk:
                # The slot's DEVICE table row stays on trash until the last
                # chunk lands: the fused block's frozen-slot re-feeds write
                # through the table at position 0, and a published row would
                # let them corrupt the half-prefilled pages.  The chunk
                # program gets the real row as an explicit argument instead.
                self._chunking[slot] = [req, 0, row]
            else:
                if row is not None:
                    self._bt[slot] = row
                    self._bt_dirty = True
                direct.append((slot, req))

        for group in self._admission_groups(direct):
            if group:
                # requests whose single token came from prefill finish here
                finished.extend(self._prefill_group(group))

        if self._chunking:
            # Prefill budget of ~C REAL tokens per step: a full long-prompt
            # chunk consumes it whole (the classic one-chunk-per-step
            # interleave, so a long prefill still never stalls running
            # decodes), while SHORT tails — shared-prefix admissions
            # prefilling only their unshared suffix — pack into one step
            # instead of trickling one admission per decode block.
            budget = self._chunk_C
            while self._chunking and budget > 0:
                done, n_real = self._chunk_step()
                finished.extend(done)
                budget -= max(n_real, 1)

        if not self._active.any():
            return finished

        # One fused pass per distinct ACTIVE tier: a pass's params must
        # match every row it advances, so other tiers' slots ride along
        # FROZEN (masked inactive).  Their idempotent K/V re-feeds do land
        # with this pass's params — wrong bytes at their frozen position —
        # but each such slot's own next active decode REWRITES that
        # position with its tier's params before anything attends to it
        # (write-before-read), so attention-family state self-repairs;
        # recurrent families are rejected at construction.  Single-tier
        # engines take exactly one pass.
        slot_tiers = np.array(
            [r.tier if r is not None else 0 for r in self._reqs], np.int32
        )
        poison_slot, poison_rel = self._poison_for()
        for tier in sorted({int(t) for t in slot_tiers[self._active]}):
            mask = self._active & (slot_tiers == tier)
            finished.extend(self._fused_pass(tier, mask, poison_slot, poison_rel))
        return finished

    def _count_attn_pages(self):
        """Add one fused block's pages to the attention page counters.

        Every slot's row goes through paged decode attention, which walks
        ``ceil(n_valid / page)`` pages of the slot's table, ``n_valid`` =
        pos + 1 (counted from the launch's positions), against the
        ``max_pages`` of a whole-table walk.
        """
        walked = np.minimum(-(-(self._pos + 1) // self.page_size), self.max_pages)
        self.attn_pages_walked += self.decode_block * int(walked.sum())
        self.attn_pages_offered += self.decode_block * self.n_slots * self.max_pages

    def _fused_pass(self, tier, mask, poison_slot, poison_rel) -> List[Request]:
        """Run one fused decode block over the slots in ``mask`` (one tier)."""
        with TraceAnnotation("engine.decode_block", tier=tier, slots=int(mask.sum())):
            self._sync_block_table()
            greedy = not (self._temps[mask] > 0).any()
            fused = self._fused_fn(greedy)
            if self.paged:
                self._count_attn_pages()
            with use_dispatch(self._dcfg):
                (
                    self.cache,
                    tokens_d,
                    pos_d,
                    active_d,
                    emitted_d,
                    quar_d,
                    toks_d,
                    emits_d,
                ) = fused(
                    self._tier_params[tier],
                    self.cache,
                    jnp.asarray(self._tokens),
                    jnp.asarray(self._pos),
                    jnp.asarray(mask),
                    jnp.asarray(self._emitted),
                    jnp.asarray(self._max_new),
                    jnp.asarray(self._seeds),
                    jnp.asarray(self._temps),
                    jnp.asarray(self._topks),
                    self._base_key,
                    jnp.int32(poison_slot),
                    jnp.int32(poison_rel),
                )
            # THE host sync for this block: drain the (n_steps, n_slots) emit
            # stack plus the final per-slot state in one transfer batch.
            with TraceAnnotation("engine.decode_sync"):
                toks = np.asarray(toks_d)
                emits = np.asarray(emits_d)
                quar = np.asarray(quar_d)
                # merge ONLY this pass's rows into the host mirrors: rows of
                # other tiers were masked inactive for this pass, and their
                # final device "active" (False) must not clobber the real
                # liveness state
                self._tokens[mask] = np.asarray(tokens_d)[mask]
                self._pos[mask] = np.asarray(pos_d)[mask]
                self._active[mask] = np.asarray(active_d)[mask]
                self._emitted[mask] = np.asarray(emitted_d)[mask]
            self.steps += self.decode_block
            self.host_syncs += 1
            self.decoded_tokens += int(emits.sum())

            finished: List[Request] = []
            for s in np.nonzero(emits.any(axis=0) | quar)[0]:
                s = int(s)
                req = self._reqs[s]
                for tok, emit in zip(toks[:, s], emits[:, s]):
                    if emit:
                        req.tokens.append(int(tok))
                if quar[s]:
                    finished.append(self._quarantine_slot(s))
                    continue
                done = self._maybe_finish(s)
                if done is not None:
                    finished.append(done)
            return finished

    # ------------------------------------------------------------------ #
    # overload machinery: preemption, quarantine, session close
    # ------------------------------------------------------------------ #
    @property
    def degraded_admissions(self) -> int:
        """Admissions the policy moved to a cheaper tier under pressure."""
        return self.scheduler.degraded

    def _poison_for(self):
        """Resolve the injector's NaN fault to (slot, step-within-block)
        for the next fused block; (-1, -1) selects nothing."""
        if self.injector is None:
            return -1, -1
        return self.injector.poison_for(
            lambda s: self._reqs[s].uid if self._reqs[s] is not None else None,
            self.n_slots,
            self.steps,
            self.decode_block,
        )

    def _pick_victim(self, head) -> Optional[int]:
        """Cheapest active slot strictly outranked by the queue head:
        lowest priority first, then fewest emitted tokens (least sunk
        work), then slot id (deterministic traces).  Mid-chunking slots
        are never victims (their pages are half-written)."""
        best, key = None, None
        for s in range(self.n_slots):
            req = self._reqs[s]
            if req is None or not self._active[s] or s in self._chunking:
                continue
            if req.priority >= head.priority:
                continue
            k = (req.priority, int(self._emitted[s]), s)
            if key is None or k < key:
                best, key = s, k
        return best

    def _preempt_for_waiters(self):
        """While the queue head outranks a running request, preempt the
        cheapest victim and retry admission.  Stops the moment an eviction
        fails to admit anyone (freeing more victims could not help: pages
        come back as warm cache, not free pages, until evicted — and the
        continuation re-queues right behind the preemptor anyway)."""
        placed = []
        while self.scheduler.queue:
            victim = self._pick_victim(self.scheduler.queue[0])
            if victim is None:
                break
            self._preempt_slot(victim)
            more = self.scheduler.admit()
            if not more:
                break
            placed.extend(more)
        return placed

    def _evict_slot(self, slot: int) -> Request:
        """Evict one running request, preserving its work, and return the
        CONTINUATION that resumes it (the caller decides where it goes —
        back into this engine's queue for preemption, or onto another
        replica for failover).

        The evicted slot's decode-filled FULL pages go through the
        standard release path — rematerialized through the prefill program
        and registered in the tier's prefix index — so the continuation's
        admission matches them read-only and prefills ONLY the unshared
        tail (plus the partial last page).  The continuation extends the
        original request's stream under the original uid/submit-time/tier:
        under greedy decoding the resumed stream is bit-identical to an
        uninterrupted run, because prefilling the extended prompt
        reproduces the same argmax chain.  Sampled (temperature > 0)
        streams resume with a fresh salt chain — eviction guarantees
        greedy parity, not sampled-stream parity.
        """
        req = self._reqs[slot]
        if self._share and len(req.tokens) > 1:
            seq = np.concatenate([req.prompt, np.asarray(req.tokens[:-1], np.int32)])
            full_end = (seq.size // self.page_size) * self.page_size
            if full_end > req.prompt.size:
                self._rematerialize(
                    slot, seq, int(req.prompt.size), full_end, req.tier
                )
            backing = self._prefix[req.tier].register(seq, self._bt[slot])
            self.page_pool.mark_indexed(backing)
        self._clear_slot(slot)
        root = req._parent if req._parent is not None else req
        if req._parent is not None:
            # fold this segment's tokens into the root NOW — the next
            # continuation starts a fresh token list of its own
            root.tokens.extend(req.tokens)
        cont = Request(
            prompt=np.concatenate([req.prompt, np.asarray(req.tokens, np.int32)]),
            max_new_tokens=req.max_new_tokens - len(req.tokens),
            sampling=req.sampling,
            extras=req.extras,
            deadline_ms=req.deadline_ms,
            min_tier=req.min_tier,
            tier=req.tier,
            priority=req.priority,
        )
        cont.uid = req.uid
        cont.t_submit = req.t_submit
        cont._parent = root
        return cont

    def _preempt_slot(self, slot: int) -> None:
        """Preempt one running request for a higher-priority waiter: evict
        it and re-queue the continuation right behind the preemptor
        (index 1)."""
        cont = self._evict_slot(slot)
        self.scheduler.queue.insert(1, cont)
        self.preemptions += 1
        if self.on_event is not None:
            self.on_event(
                "preempt",
                {"uid": cont.uid, "emitted": len(cont._parent.tokens),
                 "remaining": cont.max_new_tokens},
            )

    def snapshot_inflight(self) -> List[dict]:
        """Non-destructive view of every in-flight request (active slots
        plus mid-chunked-prefill ones) — the cluster monitor's source for
        failover accounting; touches no engine state."""
        out = []
        for slot, entry in self._chunking.items():
            req = entry[0]
            out.append(
                {"uid": req.uid, "slot": slot, "emitted": 0,
                 "remaining": req.max_new_tokens, "tier": req.tier,
                 "chunking": True}
            )
        for slot in range(self.n_slots):
            req = self._reqs[slot]
            if req is None:
                continue
            out.append(
                {
                    "uid": req.uid,
                    "slot": slot,
                    "emitted": len(req.tokens),
                    "remaining": req.max_new_tokens - len(req.tokens),
                    "tier": req.tier,
                    "chunking": False,
                }
            )
        return out

    def take_queue(self) -> List[Request]:
        """Remove and return every QUEUED (never admitted) request — the
        first half of an externally-driven drain.  The caller now owns
        their completion (re-route or shed); this engine will not touch
        them again."""
        out = list(self.scheduler.queue)
        self.scheduler.queue.clear()
        return out

    def export_inflight(self) -> List[Request]:
        """Evict EVERY in-flight request and return the requests to resume
        elsewhere — the second half of an externally-driven drain (cluster
        failover path).

        Mid-chunked-prefill slots have emitted nothing, so their ORIGINAL
        request is returned verbatim (a cold re-prefill elsewhere loses no
        work); active decode slots go through :meth:`_evict_slot`, whose
        continuation resumes bit-exactly under greedy decoding.  All slots
        and pages are released — the engine is left with no in-flight
        state, so ``PageAllocator`` invariants hold even when the export
        happens mid-fault.
        """
        out: List[Request] = []
        for slot in list(self._chunking):
            req = self._chunking.pop(slot)[0]
            self._clear_slot(slot)
            out.append(req)
            self.exported += 1
        for slot in range(self.n_slots):
            if self._reqs[slot] is not None:
                out.append(self._evict_slot(slot))
                self.exported += 1
        return out

    def _quarantine_slot(self, slot: int) -> Request:
        """Error-out one request whose decode went non-finite.

        The fused block froze the row the moment the bad logits appeared,
        so no garbage token was emitted or fed back, and the REST of the
        batch kept decoding unaffected.  The request's pages are NEVER
        registered in the prefix index — possibly-poisoned K/V must not
        back a future match — they just free for clean reuse.
        """
        req = self._reqs[slot]
        with TraceAnnotation("engine.finish", uid=req.uid):
            req.t_done = time.perf_counter()
            req.status = "error"
            req.error = "non-finite logits during decode"
            self._clear_slot(slot)
            self.quarantined += 1
            if self.on_event is not None:
                self.on_event(
                    "quarantine",
                    {"uid": req.uid, "emitted": len(req.tokens), "slot": slot},
                )
            return self._finalize(req)

    def drop_session(self, prompt) -> int:
        """Close an abandoned conversation branch NOW: drop its prefix-index
        chain (every tier) plus all registered extensions, and release the
        matching warm-cache pages for clean reuse — instead of waiting for
        LRU pressure to reclaim them.  Returns cached pages freed."""
        if self._prefix is None:
            return 0
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        freed = 0
        for index in self._prefix:
            freed += self.page_pool.drop_cached(index.drop_branch(prompt))
        return freed

    def shed_queue(self, reason: str = "shutdown") -> List[Request]:
        """Shed every QUEUED (never admitted) request with a structured
        rejection; in-flight slots are untouched.  The graceful-shutdown
        primitive: stop admitting, finish what is running."""
        while self.scheduler.queue:
            self.scheduler.shed_request(self.scheduler.queue.popleft(), reason)
        return self.scheduler.drain_shed()

    # ------------------------------------------------------------------ #
    # convenience drain loop
    # ------------------------------------------------------------------ #
    def run(
        self,
        requests: Sequence[Request],
        arrivals: Optional[Sequence[float]] = None,
        *,
        max_idle_wait: float = 0.05,
        stop: Optional[Callable[[], bool]] = None,
    ) -> List[Request]:
        """Submit ``requests`` (optionally at wall-clock ``arrivals`` offsets,
        seconds) and step until all complete.  Returns them in finish order.

        Idle handling: when no request is active and the next arrival is in
        the future, sleep EXACTLY to that arrival — but never longer than
        ``max_idle_wait`` seconds per nap, so ``has_work`` transitions from
        concurrent ``submit()`` callers are noticed promptly and a long gap
        neither busy-spins nor oversleeps past new work.

        ``stop`` (optional) is polled once per loop; the first True begins
        a GRACEFUL DRAIN: not-yet-submitted requests are dropped, queued
        ones shed with a structured ``"shutdown"`` rejection, and every
        in-flight slot decodes to completion before the loop returns — the
        SIGINT contract of launch/serve.py.
        """
        order = sorted(range(len(requests)), key=lambda i: arrivals[i] if arrivals else 0)
        t0 = time.perf_counter()
        pending = list(order)
        finished: List[Request] = []
        while pending or self.has_work:
            if stop is not None and stop():
                pending.clear()
                finished.extend(self.shed_queue("shutdown"))
                stop = None  # drained once; keep stepping in-flight slots
            now = time.perf_counter() - t0
            while pending and (arrivals is None or arrivals[pending[0]] <= now):
                self.submit(requests[pending[0]])
                pending.pop(0)
            if self.has_work:
                finished.extend(self.step())
                continue
            if pending:  # idle until the next arrival, in capped naps
                wait = arrivals[pending[0]] - (time.perf_counter() - t0)
                if wait > 0:
                    time.sleep(min(wait, max_idle_wait))
        return finished
