"""Whether what the timed path served is correct: the reference's verdict.

Once the window has closed and the program's state is freed, a sample of
the requests the window finished, drawn from the seed with the longest
one always in it, goes through the float32 reference: each prompt with
its served tokens, teacher-forced, one sequence per call. At every served
position the number read is the gap by which the served token's logit
lies below the reference's best; the run compares the widest gap of the
sample with the cell's limit. The served tokens are greedy, so a program
that computes what the configuration states serves tokens whose gap is
rounding; a token altered, a cache not written or a lower precision
serves tokens far below the best.

The first served token comes from the prefill, the rest from the arena
decode step through the slot the prefill's cache was written into, so
the sample covers prefill, slot write, decode step with its attention
kernel and the fused argmax, at the timed shapes.
"""

from __future__ import annotations

import json

import numpy as np

from chipbench import reference
from chipbench.traffic import seeded_rng
from chipbench.weights import make_weights


def sample(records, seed: int, min_tokens: int, max_requests: int) -> list:
    """Finished requests: the one with the most served tokens, then others
    in an order drawn from the seed until ``min_tokens`` served tokens or
    ``max_requests`` requests."""
    done = [r for r in records if r.req.finished >= 0]
    if not done:
        return []
    done.sort(key=lambda r: (-len(r.req.tokens), r.req.rid))
    rest = [done[i + 1] for i in seeded_rng(seed, 4).permutation(len(done) - 1)]
    out = [done[0]]
    for rec in rest:
        if sum(len(r.req.tokens) for r in out) >= min_tokens or len(out) >= max_requests:
            break
        out.append(rec)
    return out


def sequences(recs, s_pad: int):
    """Per request: the padded input (prompt, then every served token but
    the last), the served tokens as targets at the positions that
    predicted them, and the slice of positions to read."""
    for rec in recs:
        prompt = np.asarray(rec.req.prompt, np.int32)
        served = np.asarray(rec.req.tokens, np.int32)
        p, n = len(prompt), len(served)
        tokens = np.zeros(s_pad, np.int32)
        tokens[:p] = prompt
        tokens[p:p + n - 1] = served[:-1]
        targets = np.zeros(s_pad, np.int32)
        targets[p - 1:p - 1 + n] = served
        yield tokens, targets, slice(p - 1, p - 1 + n)


def gaps(config: dict, seed: int, recs, s_pad: int, control: bool = False):
    """The reference's gaps over the sampled requests: the served tokens'
    widest gap, and with ``control`` the float8 pass's widest gap."""
    import jax

    w = make_weights(config, seed)
    fn = reference.gap_fn(json.dumps(config, sort_keys=True), control)
    served, low = 0.0, 0.0
    for tokens, targets, sl in sequences(recs, s_pad):
        out = jax.device_get(fn(w, tokens, targets))
        if control:
            served = max(served, float(np.max(out[0][sl])))
            low = max(low, float(np.max(out[1][sl])))
        else:
            served = max(served, float(np.max(out[sl])))
    del w
    return (served, low) if control else served
