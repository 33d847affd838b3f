"""The comparison that decides ``correct`` for a served model.

Once the window has closed and the program's state is freed, a sample of
the greedy requests the run finished (drawn from the seed, the longest
always in it) is run through the plain reference, one causal pass over
each prompt and its served tokens.  For every served token the number
read is how far its reference logit lies below the reference's best, in
standard deviations of that reference row; the number compared is the
widest such gap.

The control puts the reference, computed in float8, in the program's
place: at every position of the same prompts and tokens it reads the gap
of the token that float8 puts first.
"""
from __future__ import annotations

from typing import List, Optional, Sequence

import numpy as np


def sample(recs: Sequence, seed: int, min_tokens: int,
           max_requests: int) -> List:
    """Finished greedy requests with their whole budget served: the one
    with the longest sequence, then others in an order drawn from the
    seed until ``min_tokens`` served tokens or ``max_requests``."""
    done = [r for r in recs
            if r.req.idx >= 0 and r.req.greedy and r.session.is_finished
            and r.session.error is None and not r.session.cancelled
            and len(r.session.generated) == r.req.max_new]
    if not done:
        return []
    done.sort(key=lambda r: r.req.idx)
    longest = max(done, key=lambda r: len(r.req.prompt) + r.req.max_new)
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng([int(seed), 4]).permutation(len(rest))
    out = [longest]
    tokens = longest.req.max_new
    for i in order:
        if tokens >= min_tokens or len(out) >= max_requests:
            break
        out.append(rest[i])
        tokens += rest[i].req.max_new
    return out


def _gap(rows: np.ndarray, picked: np.ndarray) -> np.ndarray:
    best = rows.max(-1)
    got = rows[np.arange(len(picked)), picked]
    return (best - got) / rows.std(-1)


def compare(ref, config: dict, seed: int, recs: Sequence,
            min_tokens: int, max_requests: int,
            control: bool = False) -> dict:
    """``{"worst_gap_sigma", "tokens", "requests"}`` over the sample,
    plus ``control_gap_sigma`` when asked.  Each served token is read at
    the position that produced it: the reference runs over the prompt
    and the served tokens but the last."""
    chosen = sample(recs, seed, min_tokens, max_requests)
    seqs, served = [], []
    for r in chosen:
        toks = np.asarray(r.session.generated, np.int64)
        seq = np.concatenate([np.asarray(r.req.prompt, np.int64),
                              toks[:-1]])
        seqs.append((seq, np.arange(len(r.req.prompt) - 1, len(seq))))
        served.append(toks)
    out = {"worst_gap_sigma": 0.0, "tokens": int(sum(map(len, served))),
           "requests": len(chosen)}
    if not chosen:
        return out
    rows = ref.logits(config, seed, seqs)
    out["worst_gap_sigma"] = max(float(_gap(r, t).max())
                                 for r, t in zip(rows, served))
    if control:
        low = ref.logits(config, seed, seqs, lowp="fp8")
        out["control_gap_sigma"] = max(
            float(_gap(r, lo.argmax(-1)).max()) for r, lo in zip(rows, low))
    return out


def verdict(result: dict, limit: Optional[float]) -> bool:
    """Correct when a sample was compared and its widest gap is within
    the cell's limit."""
    return bool(result["tokens"] > 0 and limit is not None
                and result["worst_gap_sigma"] <= limit)
