"""Is what the window served right?  Compared with the plain reference.

A sample of the requests that finished inside the window, drawn from the
seed, always holding the longest of them and at least a set number of
requests, is run through the reference once per request, over its prompt
and the tokens it was served (teacher forced).  For every served token the number read is how far its reference
logit lies below the reference's best logit at that position; the run's
number is the widest such gap.  Greedy decoding that computes what the
reference computes serves the reference's best token, or one within
rounding of it.
"""
from __future__ import annotations

import numpy as np

# The sample holds some hundreds of served tokens, and several requests, so
# that it spans several slots.
SAMPLE_TOKENS = 320
SAMPLE_REQUESTS = 6


def sample(done, seed: int, *, min_tokens: int = SAMPLE_TOKENS,
           min_requests: int = SAMPLE_REQUESTS) -> list:
    """Records to compare: the longest (prompt and answer) finished request,
    then others in an order drawn from the seed until ``min_tokens`` served
    tokens and ``min_requests`` requests (or every finished request, where
    fewer finished) are covered, so that the sample spans several slots."""
    done = sorted(done, key=lambda r: r.request_id)
    if not done:
        return []
    longest = max(done, key=lambda r: len(r.turn.prompt) + len(r.req.tokens))
    rng = np.random.default_rng(np.random.SeedSequence([int(seed) % 2**128,
                                                        2]))
    picked, n = [longest], len(longest.req.tokens)
    for i in rng.permutation(len(done)):
        if n >= min_tokens and len(picked) >= min_requests:
            break
        if done[i] is not longest:
            picked.append(done[i])
            n += len(done[i].req.tokens)
    return picked


def teacher_forced(records):
    """Per record: the sequence the reference reads (prompt and every served
    token but the last), the positions whose next token was served, and
    those served tokens."""
    seqs, want, served = [], [], []
    for r in records:
        prompt = np.asarray(r.turn.prompt, np.int32)
        toks = np.asarray(r.req.tokens, np.int32)
        seqs.append(np.concatenate([prompt, toks[:-1]]))
        want.append(len(prompt) - 1 + np.arange(len(toks)))
        served.append(toks)
    return seqs, want, served


def gaps(logits, tokens) -> np.ndarray:
    """How far each token's logit lies below the best logit of its row."""
    return logits.max(-1) - logits[np.arange(len(tokens)), tokens]


def widest_gap(ref, params, arch: dict, records) -> float:
    """The widest gap of a served token below the reference's best."""
    seqs, want, served = teacher_forced(records)
    lg = ref.logits(params, arch, seqs, want)
    return float(max(gaps(l, t).max() for l, t in zip(lg, served)))


def control_gap(ref, params, arch: dict, records, quant: str = "fp8"
                ) -> float:
    """The control's reading on the same prompts and served tokens: at each
    position, the gap (in the reference) of the token that the reference
    computed at ``quant`` puts first."""
    seqs, want, _ = teacher_forced(records)
    lg = ref.logits(params, arch, seqs, want)
    lq = ref.logits(params, arch, seqs, want, quant=quant)
    return float(max(gaps(l, q.argmax(-1)).max() for l, q in zip(lg, lq)))
