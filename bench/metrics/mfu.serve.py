"""Model operations of the tokens the engine processed in the engine steps
of the traced stretch (prompt tokens prefilled at their causal context,
decoded tokens at theirs; ``bench/cost/model.py``), over the wall time of
those steps times the chip's peak bf16 rate: the whole step's share of the
peak, which bounds what any one kernel's roofline share can give."""


def read(run):
    steps = [s for s in getattr(run, "steps", []) if s.traced]
    if not steps:
        return None
    trunk, head, attn = run.cost
    flops = 0
    for s in steps:
        flops += s.decode_tokens * (trunk + head) + s.decode_ctx * attn
        for p in s.prefill_done:
            flops += p * trunk + head + attn * p * (p + 1) // 2
    busy = sum(s.t1 - s.t0 for s in steps)
    return 100.0 * flops / (busy * run.peaks["bf16_flops_per_s"])
