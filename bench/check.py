"""What decides ``correct``: served tokens against the plain reference.

After the window, a sample of the finished requests (drawn from the seed,
with the longest among them) is run through the reference once each: the
prompt and the served tokens as one causal sequence.  For each served
token the gap is how far its reference logit lies below the reference's
best logit at that position; a greedy server that computes what the
reference computes serves gaps of rounding size.

The control (``control=True``) puts the reference in the program's place
at the precision below the configuration's: at each position it takes the
token the fp8 reference ranks first and reads that token's gap the same
way.
"""

from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

import traffic


def sample(finished: Sequence, k: int, seed: int) -> List:
    """Up to ``k`` finished requests: the longest, then a draw from the
    seed.  ``finished`` holds objects with ``prompt`` and ``output``."""
    if not finished:
        return []
    order = sorted(range(len(finished)),
                   key=lambda i: (-(len(finished[i].prompt)
                                    + len(finished[i].output)), i))
    first, rest = order[0], order[1:]
    rng = traffic.rng_for(seed, 7)
    pick = [rest[i] for i in rng.permutation(len(rest))[:max(0, k - 1)]]
    return [finished[first]] + [finished[i] for i in sorted(pick)]


def _padded(prompt, output, max_len: int, max_out: int):
    seq = np.concatenate([np.asarray(prompt, np.int32),
                          np.asarray(output[:-1], np.int32)])
    n = len(output)
    if len(seq) > max_len or n > max_out:
        raise ValueError(f"sequence of {len(seq)} tokens and {n} served "
                         f"exceeds the check's {max_len} / {max_out}")
    toks = np.zeros((max_len,), np.int32)
    toks[:len(seq)] = seq
    pos = np.full((max_out,), len(prompt) - 1 + n - 1, np.int32)
    pos[:n] = len(prompt) - 1 + np.arange(n)
    return toks, pos, n


def gaps(reference, w: dict, model: dict, reqs: Sequence, max_len: int,
         max_out: int, control: bool = False) -> Dict[str, np.ndarray]:
    """Per served token: ``served`` (the program's token) and, with
    ``control``, ``control`` (the fp8 reference's first choice).
    ``reference`` is the configuration's reference module: its
    ``logits_at(w, model, tokens, positions, precision)``."""
    out = {"served": [], "control": []}
    for r in reqs:
        toks, pos, n = _padded(r.prompt, r.output, max_len, max_out)
        ref = np.asarray(reference.logits_at(w, model, toks, pos))[:n]
        best = ref.max(axis=-1)
        served = np.asarray(r.output[:n])
        out["served"].append(best - ref[np.arange(n), served])
        if control:
            low = np.asarray(reference.logits_at(w, model, toks, pos,
                                                 precision="fp8"))[:n]
            pick = low.argmax(axis=-1)
            out["control"].append(best - ref[np.arange(n), pick])
    return {k: np.concatenate(v) if v else np.zeros((0,))
            for k, v in out.items()}


def numbers(g: np.ndarray) -> Dict[str, float]:
    """The numbers compared, from one set of gaps."""
    if g.size == 0:
        return {}
    return {"max_gap": float(g.max()), "mean_gap": float(g.mean())}
