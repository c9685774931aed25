"""The comparison that decides ``correct``.

Once the window has closed, a sample of the finished requests, drawn
from the seed and always holding the longest, is run through the plain
reference over its prompt and served tokens (the configuration's own
reference module, ``bench.common.load_reference``).  For each served
token the gap is how far its reference logit lies below the reference's
best at that position.  Greedy decoding of an exact program gives 0; rounding
flips near-ties.  A cell's file names the numbers it compares and their
limits; the others are reported beside them.  The widest gap did not
separate the control from the program by the factor of three a limit
needs (its maximum swings with the few near-ties a sample happens to
hold), and greedy decoding of random weights can loop over one near-tie
for hundreds of tokens, so ``gap_mean_req`` weighs each request alike.

The control (not run by the benchmark's own runs) puts the reference
one precision step below the configuration's in the program's place: at
the same positions it takes the token the lower precision ranks first
and reads that token's gap in the float32 reference.
"""
from __future__ import annotations

import numpy as np

# a sample holds the longest request, then random finished ones until it
# holds this many served tokens (or MAX_REQUESTS requests)
SAMPLE_TOKENS = 2048
MAX_REQUESTS = 32
LOWER = {"float32": "bfloat16", "bfloat16": "fp8"}


def sample(trails, seed):
    """Finished trails to compare: the longest, then random ones drawn
    from the seed."""
    done = sorted((t for t in trails
                   if t.outcome == "finished" and t.tokens),
                  key=lambda t: t.uid)
    if not done:
        return []
    picked = [max(done, key=lambda t: (len(t.tokens), -t.uid))]
    rng = np.random.default_rng([int(seed) % (1 << 64), 0xC4EC])
    rest = [t for t in done if t not in picked]
    rng.shuffle(rest)
    for t in rest:
        if (sum(len(p.tokens) for p in picked) >= SAMPLE_TOKENS
                or len(picked) >= MAX_REQUESTS):
            break
        picked.append(t)
    return picked


def token_gaps(ref, spec, params, prompt, tokens, first, last,
               control=None):
    """Gaps of ``tokens[first:last]`` (served after ``prompt`` and the
    tokens before them), by the reference module ``ref``.
    ``control=(precision, params)`` reads the tokens that the reference
    computed in ``precision`` ranks first instead of the served ones."""
    seq = np.concatenate([prompt, np.asarray(tokens[:-1], np.int32)])
    pos = np.arange(len(prompt) - 1 + first, len(prompt) - 1 + last)
    lg = np.asarray(ref.logits_at(spec, params, seq, pos))
    served = np.asarray(tokens[first:last])
    if control is not None:
        precision, cparams = control
        served = np.asarray(ref.logits_at(spec, cparams, seq, pos,
                                          precision)).argmax(-1)
    best = lg.max(-1)
    return best - lg[np.arange(len(served)), served]


def widest(gaps):
    return float(max((g.max() for g in gaps if g.size), default=0.0))


def mean(gaps):
    n = sum(g.size for g in gaps)
    return float(sum(g.sum() for g in gaps) / n) if n else 0.0


def readings(gaps, limits):
    """The numbers a cell reports, each with its limit from ``limits``
    (None where the cell compares it with nothing): the mean gap over
    tokens, the mean over requests of each one's mean gap, the widest
    gap, the share of tokens that are not the reference's best, and how
    many tokens were compared."""
    n = sum(g.size for g in gaps)
    flipped = sum(int((g > 0).sum()) for g in gaps)
    per_req = [float(g.mean()) for g in gaps if g.size]
    numbers = {"gap_mean": mean(gaps),
               "gap_mean_req": (sum(per_req) / len(per_req)
                                if per_req else 0.0),
               "gap_max": widest(gaps),
               "flip_share": flipped / n if n else 0.0,
               "tokens": n}
    unknown = set(limits) - set(numbers)
    if unknown:
        raise KeyError(f"limits for numbers no cell reads: {sorted(unknown)}")
    return {k: (v, limits.get(k)) for k, v in numbers.items()}
