"""Serving cells: build the engine, warm every shape the traffic uses, then
offer the traffic for the window, open loop, timed by the host clock from
each request's due time."""

from __future__ import annotations

import dataclasses
import math
import time
from typing import Dict, List

import numpy as np

from bench.harness import traffic as gen

# engine counters read before and after the window
COUNTERS = ("steps", "decoded_tokens", "prefill_chunks", "host_syncs",
            "skipped_prefill_tokens", "shared_page_hits", "cow_forks", "preemptions")


@dataclasses.dataclass
class Record:
    """One request as the harness saw it; times are perf_counter seconds."""

    item: gen.Item
    req: object  # repro.serving.Request
    due: float  # absolute due time
    sent: float = 0.0

    @property
    def ok(self) -> bool:
        r = self.req
        return r.status == "ok" and r.t_done > 0 and len(r.tokens) == r.max_new_tokens


@dataclasses.dataclass
class Step:
    t0: float
    t1: float
    emitted: int  # output tokens this engine step produced
    decode_ctx: int  # sum over decode-produced tokens of the context attended
    decode_tokens: int
    prefill_done: List[int]  # prompt tokens prefilled for requests whose first token came here
    grouped: int = 0  # of those, requests served by grouped (not chunked) prefill
    traced: bool = False


def _next_pow2(x: int, lo: int = 1) -> int:
    p = lo
    while p < x:
        p *= 2
    return p


class Server:
    """The engine under test."""

    def __init__(self, model, params, config: dict, mix: dict):
        from repro.runtime.dispatch import DispatchConfig
        from repro.serving import Engine

        eng_cfg = config["engine"]
        self.mix = mix
        self.max_len = gen.max_len(mix)
        self.page = int(eng_cfg["page_size"])
        self.chunk = int(eng_cfg["prefill_chunk"])
        self.n_slots = int(eng_cfg["n_slots"])
        self.group_warm = int(eng_cfg["prefill_group_warm"])
        max_pages = -(-self.max_len // self.page)
        if eng_cfg.get("kv_pool_bytes"):
            kv_pages = min(int(eng_cfg["kv_pool_bytes"] // page_bytes(model, self.page)),
                           self.n_slots * max_pages)
        else:
            kv_pages = self.n_slots * max_pages
        self.kv_pages = kv_pages
        dcfg = DispatchConfig.from_arch(model.cfg)
        self.engine = Engine(
            model, params, n_slots=self.n_slots, max_len=self.max_len,
            dispatch=dcfg, decode_block=int(eng_cfg["decode_block"]),
            page_size=self.page, kv_pages=kv_pages, prefill_chunk=self.chunk,
            share_prefix=bool(eng_cfg["share_prefix"]),
        )

    # ------------------------------------------------------------------ #
    def warm_up(self, vocab: int, rng) -> int:
        """Run every program shape the traffic can reach once; returns how
        many requests that took.

        Prompts longer than the chunk go through the one (1, chunk) chunk
        program; shorter ones through grouped prefill, whose program and
        page scatter the engine buckets to a power-of-two group size G and
        (the program only) a power-of-two prompt length.  G is warmed up to
        the configuration's ``prefill_group_warm``: the groups the traffic
        forms, and the largest whose scatter fits beside the pool.  Shared
        prefixes add the copy-on-write page fork and the decode-page
        rematerialization.
        """
        from repro.serving import Request

        eng = self.engine
        n = 0

        def serve(prompts, max_new=2):
            nonlocal n
            reqs = [Request(prompt=p, max_new_tokens=max_new) for p in prompts]
            for r in reqs:
                eng.submit(r)
            while eng.has_work:
                eng.step()
            for r in reqs:
                if r.status != "ok":
                    raise SystemExit(f"bench: warm-up request ended {r.status}: {r.error}")
            n += len(reqs)

        lo = gen.min_prompt(self.mix)
        if lo <= self.chunk:
            G = 1
            while G <= self.group_warm:
                P = _next_pow2(lo, 8)
                while P <= self.chunk:
                    serve([rng.integers(0, vocab, P) for _ in range(G)])
                    P *= 2
                G *= 2
        # the chunk program, a prefix match, a copy-on-write fork, and
        # decode pages rematerialized at release (max_new crosses a page)
        long = rng.integers(0, vocab, min(2 * self.chunk + 7, self.max_len - self.page - 12))
        serve([long], max_new=self.page + 2)
        serve([long])
        serve([long[: 2 * self.page]])
        serve([np.concatenate([long, rng.integers(0, vocab, 9)])])
        eng.reset_prefix_cache()
        return n

    def pool_shapes(self) -> List[str]:
        """HLO shape text of the engine's page-pool leaves."""
        import jax

        from bench.harness.trace import shape_text

        pools = {k: v for k, v in self.engine.cache.items() if k != "block_table"}
        return [shape_text(l.dtype, l.shape) for l in jax.tree_util.tree_leaves(pools)]

    def counters(self) -> Dict[str, int]:
        return {k: int(getattr(self.engine, k)) for k in COUNTERS}

    # ------------------------------------------------------------------ #
    def _step(self, live: List[Record], steps: List[Step], traced: bool) -> list:
        eng = self.engine
        before = [len(r.req.tokens) for r in live]
        t0 = time.perf_counter()
        if traced:
            import jax

            with jax.profiler.TraceAnnotation("bench.step"):
                done = eng.step()
        else:
            done = eng.step()
        t1 = time.perf_counter()
        emitted = ctx = dec = grouped = 0
        prefilled = []
        for rec, b in zip(live, before):
            a = len(rec.req.tokens)
            if a == b:
                continue
            emitted += a - b
            plen = int(rec.req.prompt.size)
            if b == 0:
                prefilled.append(plen - int(rec.req.prefill_skipped))
                grouped += int(rec.req.prefill_skipped) == 0 and plen <= self.chunk
            j0 = max(b, 1)
            if a > j0:  # decode-produced tokens j0..a-1 attend plen + j positions
                dec += a - j0
                ctx += (a - j0) * plen + (j0 + a - 1) * (a - j0) // 2
        steps.append(Step(t0, t1, emitted, ctx, dec, prefilled, grouped, traced))
        return done

    def run_open(self, items: List[gen.Item], seconds: float, drain_s: float,
                 tracer=None) -> tuple:
        """Submit each item at its due time; step whenever there is work.

        Starting and stopping the profiler holds the loop for seconds; the
        offered load's clock stands still meanwhile (``paused``), so a traced
        run meets the same arrivals as an untraced one and not a burst of
        every request that fell due while the trace was written."""
        from repro.serving import Request, SamplingParams

        eng = self.engine
        records: List[Record] = []
        live: List[Record] = []
        steps: List[Step] = []
        t0 = time.perf_counter()
        paused = 0.0
        nxt = 0
        tracing = False
        while True:
            now = time.perf_counter()
            if tracer is not None and not tracing and now >= t0 + paused + tracer.start_s:
                tracer.start(self)
                tracing = True
                paused += time.perf_counter() - now
            elif tracing and now >= t0 + paused + tracer.stop_s:
                tracer.stop(self)
                tracing, tracer = False, None
                paused += time.perf_counter() - now
            now = time.perf_counter()
            close = t0 + paused + seconds
            while nxt < len(items) and t0 + paused + items[nxt].due <= now:
                it = items[nxt]
                req = Request(prompt=it.prompt, max_new_tokens=it.max_new,
                              sampling=SamplingParams())
                rec = Record(it, req, due=t0 + paused + it.due, sent=time.perf_counter())
                eng.submit(req)
                records.append(rec)
                live.append(rec)
                nxt += 1
            if eng.has_work:
                done = self._step(live, steps, tracing)
                if done:
                    ids = {id(r) for r in done}
                    live = [r for r in live if id(r.req) not in ids]
            elif nxt < len(items):
                time.sleep(max(0.0, min(t0 + paused + items[nxt].due - time.perf_counter(),
                                        0.002)))
            else:
                break
            if now > close + drain_s:
                break
        if tracing:
            tracer.stop(self)
        if paused:
            print(f"[serve] the profiler held the loop {paused:.3f} s; arrivals waited",
                  flush=True)
        return t0, records, steps


def page_bytes(model, page: int) -> int:
    """Bytes one page adds to the paged cache, from the cache's own shapes."""
    import jax

    def total(n):
        cache = jax.eval_shape(lambda: model.init_cache_paged(1, page, page, n)[0])
        leaves = [c for k, c in cache.items() if k != "block_table"]
        return sum(math.prod(l.shape) * l.dtype.itemsize for l in jax.tree_util.tree_leaves(leaves))

    return total(2) - total(1)
