"""Whether the served tokens are right: each sampled request's prompt and
served tokens go once through the plain reference the configuration names
(``bench/reference/<config["reference"]>.py``), and the number compared
is the widest gap by which a served token's reference logit lies below the
reference's best at that position.  Greedy decoding makes the gap 0 up to
rounding; a wrong token, cache entry or kernel opens it."""

from __future__ import annotations

from typing import List

import numpy as np

from bench.harness import seeds, spec


def sample(records, seed: int, n: int) -> List[object]:
    """The longest finished request and ``n - 1`` others drawn from the seed."""
    done = [r for r in records if r.ok]
    if not done:
        return []
    longest = max(range(len(done)), key=lambda i: done[i].req.prompt.size + len(done[i].req.tokens))
    rest = [i for i in range(len(done)) if i != longest]
    pick = seeds.rng(seed, seeds.SAMPLE).permutation(rest)[: n - 1]
    return [done[longest]] + [done[i] for i in sorted(pick)]


def gaps(params, config: dict, recs, *, control: bool = False):
    """(worst gap, tokens compared).  With ``control`` the tokens judged are
    those the float8 reference would put first at each position."""
    import jax.numpy as jnp

    reference = spec.load_reference(config["reference"])
    worst, n = 0.0, 0
    for rec in recs:
        prompt = np.asarray(rec.req.prompt, np.int32)
        toks = np.asarray(rec.req.tokens, np.int32)
        seq = np.concatenate([prompt, toks[:-1]])
        rows = np.arange(prompt.size - 1, seq.size)
        x = reference.hidden(params, seq, config)
        ref = reference.logits_rows(params, x, rows, config)
        if control:
            xc = reference.hidden(params, seq, config, fp8=True)
            low = reference.logits_rows(params, xc, rows, config, fp8=True)
        else:
            low = None
        for r, lg in ref:
            if low is not None:
                _, lc = next(low)
                pick = jnp.argmax(lc, axis=-1)
            else:
                pick = jnp.asarray(toks[r - (prompt.size - 1)])
            best = jnp.max(lg, axis=-1)
            chosen = jnp.take_along_axis(lg, pick[:, None], axis=-1)[:, 0]
            worst = max(worst, float(jnp.max(best - chosen)))
            n += len(r)
    return worst, n
