"""Unified kernel-dispatch runtime: shape/platform-aware execution layer.

Replaces the ad-hoc ``use_pallas`` flag that used to be hand-threaded through
every model signature.  All backend selection — Pallas vs XLA vs reference,
fused vs two-GEMM vs dense-rematerialized, interpret-mode detection, VMEM
residency budgeting — lives here, in ONE policy layer, and the model zoo
calls shape-only entry points (``lowrank_apply``, ``dense_apply``, ...).

Usage mirrors ``sharding.rules.use_rules``:

    from repro.runtime.dispatch import DispatchConfig, use_dispatch

    with use_dispatch(DispatchConfig.from_arch(cfg)):
        logits, cache = model.prefill(params, batch, max_len)

Outside any context a default ``DispatchConfig()`` (backend="auto") applies,
so model code keeps working standalone (tests, notebooks) with the same
platform-appropriate choices.

Selection is made at TRACE time (shapes and platform are static), so each
decision is recorded once per traced call site in the hit counters —
``counters()`` / ``format_counters()`` let benchmarks report exactly which
path every linear in a compiled program took.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
import threading
from collections import Counter
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels.decode_attention import (
    decode_attention_pallas,
    paged_decode_attention_pallas,
)
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.lowrank_matmul import (
    DEFAULT_VMEM_LIMIT,
    fused_vmem_bytes,
    lowrank_matmul_batched_pallas,
    lowrank_matmul_pallas,
)
from repro.kernels.sketch_matmul import sketch_matmul_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas

__all__ = [
    "BACKENDS",
    "OPS",
    "PATH_DENSE",
    "PATH_TWO_GEMM",
    "PATH_FUSED",
    "PATH_FUSED_BATCHED",
    "DispatchConfig",
    "active_dispatch",
    "use_dispatch",
    "choose_lowrank_path",
    "choose_decode_path",
    "choose_paged_decode_path",
    "lowrank_apply",
    "dense_apply",
    "sketch_matmul",
    "ssd_scan",
    "flash_attention",
    "decode_attention",
    "paged_decode_attention",
    "counters",
    "counters_by_path",
    "reset_counters",
    "format_counters",
]

BACKENDS = ("auto", "xla", "pallas", "reference")
OPS = (
    "dense",
    "lowrank_matmul",
    "sketch_matmul",
    "ssd_scan",
    "flash_attention",
    "decode_attention",
    "paged_decode_attention",
)

# auto table: below this cache depth the flash-decode kernel's grid overhead
# exceeds what the dense einsum costs, so short caches stay on XLA
DECODE_MIN_SEQ = 128

# low-rank execution paths (what the auto table chooses between)
PATH_DENSE = "dense"  # materialize A @ B once, single GEMM (rank >= break-even)
PATH_TWO_GEMM = "two_gemm"  # (x @ A) @ B in XLA; (M, r) intermediate via HBM
PATH_FUSED = "fused"  # Pallas kernel, intermediate resident in VMEM
PATH_FUSED_BATCHED = "fused_batched"  # stacked (L, ...) fused kernel


@dataclasses.dataclass(frozen=True)
class DispatchConfig:
    """One immutable policy object injected once (MaxText-style) instead of a
    bool threaded through ~25 call sites.

    backend   : "auto" (shape/platform table) | "xla" | "pallas" | "reference"
    overrides : per-op backend pins, e.g. (("flash_attention", "xla"),)
    vmem_limit_bytes : dtype-aware residency budget for the fused path
                       (replaces the old static MAX_RANK/MAX_N constants)
    dense_min_tokens : flattened token count above which an over-break-even
                       rank is rematerialized to a dense GEMM
    fused_min_rank : ranks BELOW this never take the fused Pallas path —
                     elastic-rank tiers (core.lowrank.slice_rank) can slice
                     factors down to rank 1-2, where the kernel's rank-tile
                     grid is almost entirely padding and two thin XLA GEMMs
                     win; each tier's program re-traces, so the same config
                     routes each tier to its own best path
    interpret : force Pallas interpret mode; None = infer (non-TPU backends
                cannot lower Pallas-TPU natively)
    """

    backend: str = "auto"
    overrides: Tuple[Tuple[str, str], ...] = ()
    vmem_limit_bytes: int = DEFAULT_VMEM_LIMIT
    dense_min_tokens: int = 2048
    fused_min_rank: int = 2
    interpret: Optional[bool] = None

    def __post_init__(self):
        if self.backend not in BACKENDS:
            raise ValueError(f"backend {self.backend!r} not in {BACKENDS}")
        for op, be in self.overrides:
            if op not in OPS:
                raise ValueError(f"override op {op!r} not in {OPS}")
            if be not in BACKENDS:
                raise ValueError(f"override backend {be!r} not in {BACKENDS}")

    @classmethod
    def from_arch(cls, cfg, **kw) -> "DispatchConfig":
        """Build from an ArchConfig's ``kernels`` field (``use_pallas`` is
        folded into ``kernels`` by ArchConfig itself, as a deprecated alias)."""
        return cls(backend=getattr(cfg, "kernels", "auto"), **kw)

    def backend_for(self, op: str) -> str:
        for o, be in self.overrides:
            if o == op:
                return be
        return self.backend

    def replace(self, **kw) -> "DispatchConfig":
        return dataclasses.replace(self, **kw)

    def interpret_mode(self, platform: Optional[str] = None) -> bool:
        """Whether Pallas kernels run interpreted: the pinned ``interpret``,
        else True off a TPU (only a TPU lowers Pallas-TPU natively)."""
        if self.interpret is not None:
            return self.interpret
        return _platform(platform) != "tpu"


_state = threading.local()
_DEFAULT = DispatchConfig()


def active_dispatch() -> DispatchConfig:
    return getattr(_state, "dispatch", None) or _DEFAULT


@contextlib.contextmanager
def use_dispatch(config: Optional[DispatchConfig] = None, **kw):
    """Install a DispatchConfig for the dynamic extent (mirrors use_rules).

    Keyword form: ``use_dispatch(backend="pallas")``.  Must be active while
    the model function is TRACED (jit tracing happens on first call)."""
    if config is None:
        config = DispatchConfig(**kw)
    elif kw:
        config = config.replace(**kw)
    prev = getattr(_state, "dispatch", None)
    _state.dispatch = config
    try:
        yield config
    finally:
        _state.dispatch = prev


# --------------------------------------------------------------------------- #
# hit counters (trace-time): (op, path, shape-signature) -> count
# --------------------------------------------------------------------------- #
_COUNTS: Counter = Counter()  # guarded by: _COUNTS_LOCK
_COUNTS_LOCK = threading.Lock()


def _record(op: str, path: str, sig: tuple):
    with _COUNTS_LOCK:
        _COUNTS[(op, path, sig)] += 1


def counters() -> dict:
    """{(op, path, shape_sig): hits} — one entry per distinct traced site."""
    with _COUNTS_LOCK:
        return dict(_COUNTS)


def counters_by_path() -> dict:
    """{(op, path): hits} aggregated over shapes."""
    agg: Counter = Counter()
    for (op, path, _sig), n in counters().items():
        agg[(op, path)] += n
    return dict(agg)


def reset_counters():
    with _COUNTS_LOCK:
        _COUNTS.clear()


def format_counters() -> str:
    rows = sorted(counters().items())
    if not rows:
        return "(no dispatched ops recorded)"
    return "\n".join(
        f"{op:16s} {path:14s} {str(sig):32s} x{n}" for (op, path, sig), n in rows
    )


# --------------------------------------------------------------------------- #
# auto selection table
# --------------------------------------------------------------------------- #
def _platform(platform: Optional[str]) -> str:
    return platform if platform is not None else jax.default_backend()


def _break_even_rank(d_in: int, d_out: int) -> int:
    return (d_in * d_out - 1) // (d_in + d_out)


def _lowrank_dims(x_shape, a_shape, b_shape):
    """(n_stack_dims, L, M, K, r, N) for a possibly-stacked factored apply."""
    nl = len(a_shape) - 2
    if len(b_shape) != len(a_shape):
        raise ValueError(f"A/B rank mismatch: A {a_shape}, B {b_shape}")
    if nl and (a_shape[:nl] != b_shape[:nl] or tuple(x_shape[:nl]) != a_shape[:nl]):
        raise ValueError(
            f"stacked lowrank apply: leading dims disagree "
            f"(x {x_shape}, A {a_shape}, B {b_shape})"
        )
    if x_shape[-1] != a_shape[-2]:
        raise ValueError(
            f"lowrank apply: x contraction dim {x_shape[-1]} != A rows "
            f"{a_shape[-2]} (x {x_shape}, A {a_shape})"
        )
    L = math.prod(a_shape[:nl]) if nl else 1
    M = math.prod(x_shape[nl:-1]) if len(x_shape) - nl > 1 else 1
    return nl, L, M, a_shape[-2], a_shape[-1], b_shape[-1]


def choose_lowrank_path(
    x_shape,
    a_shape,
    b_shape,
    dtype,
    *,
    config: Optional[DispatchConfig] = None,
    platform: Optional[str] = None,
) -> str:
    """The auto selection table: dense / two-GEMM / fused per call site.

    Inputs are static (shapes, dtype, platform), so this is a pure trace-time
    decision.  ``platform`` is injectable for tests.
    """
    config = config or active_dispatch()
    platform = _platform(platform)
    nl, _L, M, K, r, N = _lowrank_dims(x_shape, a_shape, b_shape)
    be = config.backend_for("lowrank_matmul")
    fused = PATH_FUSED_BATCHED if nl else PATH_FUSED
    # rank floor: a prefix-sliced tier (core.lowrank.slice_rank) can carry
    # rank 1-2 factors, where the fused kernel's rank tile is ~all padding
    fits = (
        fused_vmem_bytes(r, N, dtype) <= config.vmem_limit_bytes
        and r >= config.fused_min_rank
    )

    if be == "reference":
        return PATH_TWO_GEMM
    if be == "pallas":
        # forced Pallas still may not oversubscribe VMEM (or undershoot rank)
        return fused if fits else PATH_TWO_GEMM
    if be == "auto" and platform == "tpu" and fits:
        return fused
    # XLA (or auto off-TPU / non-resident): if the rank exceeds break-even the
    # factored form is MORE flops than dense — rematerialize W once when the
    # token batch amortizes the (K, r) @ (r, N) remat.
    if r >= _break_even_rank(K, N) and M >= config.dense_min_tokens:
        return PATH_DENSE
    return PATH_TWO_GEMM


# --------------------------------------------------------------------------- #
# execution entry points
# --------------------------------------------------------------------------- #
def dense_apply(x: jax.Array, w: jax.Array) -> jax.Array:
    """y = x @ W (dense kernel) with fp32 MXU accumulation."""
    _record("dense", "xla", (x.shape[-1], w.shape[-1]))
    return jnp.matmul(x, w, preferred_element_type=jnp.float32).astype(x.dtype)


def lowrank_apply(x: jax.Array, A: jax.Array, B: jax.Array) -> jax.Array:
    """y = (x @ A) @ B via whichever path the dispatch table selects.

    2-D factors: x (..., K), A (K, r), B (r, N) — leading x dims flattened.
    Stacked factors: A (L..., K, r), B (L..., r, N) with x (L..., M..., K) —
    the scan/expert-stacked case.  Every path canonicalizes the stacked case
    to (L, M, K) @ (L, K, r) @ (L, r, N) first, so fused and fallback paths
    agree for any leading-dim layout (bare jnp.matmul broadcasting would
    crash or silently misalign inner batch dims against the stack).
    """
    config = active_dispatch()
    platform = _platform(None)
    path = choose_lowrank_path(
        x.shape, A.shape, B.shape, x.dtype, config=config, platform=platform
    )
    nl, L, M, K, r, N = _lowrank_dims(x.shape, A.shape, B.shape)
    _record("lowrank_matmul", path, (L, M, K, r, N))
    out_shape = x.shape[:-1] + (N,)
    if nl:
        xc, Ac, Bc = x.reshape(L, M, K), A.reshape(L, K, r), B.reshape(L, r, N)
    else:
        xc, Ac, Bc = x, A, B  # 2-D factors broadcast over any x leading dims

    if path == PATH_DENSE:
        w = jnp.matmul(
            Ac.astype(jnp.float32), Bc.astype(jnp.float32)
        ).astype(x.dtype)
        y = jnp.matmul(xc, w, preferred_element_type=jnp.float32).astype(x.dtype)
        return y.reshape(out_shape)
    if path == PATH_FUSED:
        y = lowrank_matmul_pallas(
            xc.reshape(-1, K), Ac, Bc,
            interpret=config.interpret_mode(platform),
            vmem_limit=config.vmem_limit_bytes,
        )
        return y.reshape(out_shape)
    if path == PATH_FUSED_BATCHED:
        y = lowrank_matmul_batched_pallas(
            xc, Ac, Bc,
            interpret=config.interpret_mode(platform),
            vmem_limit=config.vmem_limit_bytes,
        )
        return y.reshape(out_shape)
    # two-GEMM fallback IS the reference implementation for this op
    return _ref.lowrank_matmul_ref(xc, Ac, Bc).reshape(out_shape)


def _use_pallas(op: str, config: DispatchConfig, platform: str) -> bool:
    be = config.backend_for(op)
    if be == "pallas":
        return True
    if be in ("xla", "reference"):
        return False
    return platform == "tpu"  # auto: interpret-mode Pallas is a debug tool


def sketch_matmul(a: jax.Array, b: jax.Array) -> jax.Array:
    """(M, K) @ (K, N) — RSI sketch GEMM."""
    config = active_dispatch()
    platform = _platform(None)
    if _use_pallas("sketch_matmul", config, platform):
        _record("sketch_matmul", "pallas", (a.shape, b.shape))
        return sketch_matmul_pallas(a, b, interpret=config.interpret_mode(platform))
    _record("sketch_matmul", "xla", (a.shape, b.shape))
    return _ref.sketch_matmul_ref(a, b)


def ssd_scan(x, dt, B_in, C_in, A, *, chunk: int = 128):
    """Mamba2 SSD chunked scan.  Returns (y, final_state)."""
    config = active_dispatch()
    platform = _platform(None)
    if _use_pallas("ssd_scan", config, platform):
        _record("ssd_scan", "pallas", (x.shape, chunk))
        return ssd_scan_pallas(
            x, dt, B_in, C_in, A, chunk=chunk, interpret=config.interpret_mode(platform)
        )
    _record("ssd_scan", "xla", (x.shape, chunk))
    xbar = (x.astype(jnp.float32) * dt[..., None].astype(jnp.float32)).astype(x.dtype)
    return _ref.ssd_scan_ref(xbar, dt, B_in, C_in, A)


def flash_attention(q, k, v, *, causal: bool = True):
    """Forward-only flash attention (prefill hot path)."""
    config = active_dispatch()
    platform = _platform(None)
    if _use_pallas("flash_attention", config, platform):
        _record("flash_attention", "pallas", (q.shape, causal))
        return flash_attention_pallas(
            q, k, v, causal=causal, interpret=config.interpret_mode(platform)
        )
    _record("flash_attention", "xla", (q.shape, causal))
    return _ref.flash_attention_ref(q, k, v, causal=causal)


def choose_decode_path(
    q_shape,
    kv_shape,
    *,
    config: Optional[DispatchConfig] = None,
    platform: Optional[str] = None,
) -> str:
    """Auto table for one-token decode attention: "pallas" or "xla".

    Like ``choose_lowrank_path`` this is a pure trace-time decision over
    static shapes and platform: the flash-decode kernel wins on TPU once the
    cache is deep enough to amortize its grid (DECODE_MIN_SEQ); short caches
    and non-TPU platforms take the dense einsum reference.  A pinned
    "pallas" backend always takes the kernel (interpret mode off-TPU);
    "xla"/"reference" always take the einsum.
    """
    config = config or active_dispatch()
    platform = _platform(platform)
    be = config.backend_for("decode_attention")
    if be == "pallas":
        return "pallas"
    if be in ("xla", "reference"):
        return "xla"
    S = kv_shape[1]
    # the kernel's mask block puts the sequence block on the lane dim, which
    # Mosaic tiles by 128: misaligned depths stay on the einsum
    if platform == "tpu" and S >= DECODE_MIN_SEQ and S % 128 == 0:
        return "pallas"
    return "xla"


def decode_attention(q, k_cache, v_cache, valid):
    """One-token GQA attention over a cache (the serving decode hot path).

    q: (B, 1, H, hd); k_cache: (B, S, KV, hd); v_cache: (B, S, KV, vd);
    valid: (B, S) bool strict per-slot mask.  Fully-masked rows produce
    zeros (see kernels/ref.decode_attention_ref).
    """
    config = active_dispatch()
    platform = _platform(None)
    path = choose_decode_path(q.shape, k_cache.shape, config=config, platform=platform)
    B, _, H, hd = q.shape
    S, KV = k_cache.shape[1], k_cache.shape[2]
    _record("decode_attention", path, (B, S, KV, H // KV, hd))
    if path == "pallas":
        return decode_attention_pallas(
            q, k_cache, v_cache, valid, interpret=config.interpret_mode(platform)
        )
    return _ref.decode_attention_ref(q, k_cache, v_cache, valid)


def choose_paged_decode_path(
    q_shape,
    pool_shape,
    n_tbl: int,
    *,
    config: Optional[DispatchConfig] = None,
    platform: Optional[str] = None,
) -> str:
    """Auto table for BLOCK-TABLE decode attention: "pallas" or "xla".

    Same shape logic as :func:`choose_decode_path` with the cache depth
    measured LOGICALLY (``n_tbl`` block-table entries x page tokens): on TPU
    a deep-enough virtual sequence amortizes the paged kernel's grid, while
    short tables and non-TPU platforms take the gather-einsum reference
    (kernels/ref.paged_decode_attention_ref).  Pins behave as everywhere
    else: "pallas" forces the kernel (interpret off-TPU), "xla"/"reference"
    force the gather.
    """
    config = config or active_dispatch()
    platform = _platform(platform)
    be = config.backend_for("paged_decode_attention")
    if be == "pallas":
        return "pallas"
    if be in ("xla", "reference"):
        return "xla"
    if platform == "tpu" and n_tbl * pool_shape[1] >= DECODE_MIN_SEQ:
        return "pallas"
    return "xla"


def paged_decode_attention(q, k_pool, v_pool, block_table, n_valid):
    """One-token GQA attention through a paged KV pool (continuous batching).

    q: (B, 1, H, hd); pools: (P, page, KV, hd/vd) physical pages shared by
    every slot; block_table: (B, n_tbl) int32 page ids; n_valid: (B,) int32
    valid logical positions.  The Pallas kernel streams pages through the
    block table with scalar-prefetch index maps (no per-slot gather is ever
    materialized); the XLA path gathers and defers to the flat einsum
    oracle.  Fully-masked rows produce zeros on both paths.
    """
    config = active_dispatch()
    platform = _platform(None)
    n_tbl = block_table.shape[1]
    path = choose_paged_decode_path(
        q.shape, k_pool.shape, n_tbl, config=config, platform=platform
    )
    B, _, H, hd = q.shape
    P, page, KV = k_pool.shape[0], k_pool.shape[1], k_pool.shape[2]
    _record("paged_decode_attention", path, (B, P, page, n_tbl, KV, H // KV, hd))
    if path == "pallas":
        return paged_decode_attention_pallas(
            q, k_pool, v_pool, block_table, n_valid,
            interpret=config.interpret_mode(platform),
        )
    return _ref.paged_decode_attention_ref(q, k_pool, v_pool, block_table, n_valid)
