"""Share of the window's prompt tokens that admission served from shared
prefix pages (the engine's per-request ``prefill_skipped`` counter)."""


def read(run):
    recs = getattr(run, "records", None)
    if not recs:
        return None
    prompt = sum(int(r.req.prompt.size) for r in recs)
    return 100.0 * sum(int(r.req.prefill_skipped) for r in recs) / prompt
