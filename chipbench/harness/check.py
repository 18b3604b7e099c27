"""What decides ``correct``: served tokens against the plain reference.

Once the window has closed, a sample of the finished requests, drawn from
the seed and always holding the one with the most served tokens, is run
through the reference over its prompt and served tokens.  The numbers
compared come from the gap by which each served token's logit lies below the
reference's best logit at its position (greedy decoding: a sound server
picks the reference's best up to rounding, so the gap is rounding-sized):
the widest gap, and the mean over every served token.  A cell's limits file
names the numbers it holds and their limits.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

from harness.traffic import rng_for


def sample(finished: List[Tuple[int, np.ndarray, np.ndarray]], n: int,
           seed: int) -> List[Tuple[int, np.ndarray, np.ndarray]]:
    """``finished``: (rid, prompt, served tokens).  The longest, and others
    drawn from the seed, ``n`` at most, in rid order."""
    if not finished:
        return []
    by_rid = sorted(finished, key=lambda f: f[0])
    longest = max(by_rid, key=lambda f: (len(f[2]), -f[0]))
    rest = [f for f in by_rid if f[0] != longest[0]]
    rng = rng_for(seed, "check")
    pick = rng.permutation(len(rest))[: max(n - 1, 0)]
    chosen = [longest] + [rest[i] for i in sorted(pick)]
    return sorted(chosen, key=lambda f: f[0])


# the reference runs over blocks of BLOCK requests, sorted by length, each
# padded to its longest rounded up to STRIDE: a few shapes per mix, and
# little padding
BLOCK = 4
STRIDE = 256


def blocks(chosen) -> List[Tuple[np.ndarray, np.ndarray]]:
    """(seqs, served), both (BLOCK, T), per block: each row a request's
    prompt and served tokens but the last, and at the position whose logits
    chose each served token, that token (-1 elsewhere)."""
    ordered = sorted(chosen, key=lambda f: (len(f[1]) + len(f[2]), f[0]))
    out = []
    for i in range(0, len(ordered), BLOCK):
        part = ordered[i:i + BLOCK]
        T = max(len(p) + len(t) - 1 for _, p, t in part)
        T = -(-T // STRIDE) * STRIDE
        seqs = np.zeros((BLOCK, T), np.int32)
        served = np.full((BLOCK, T), -1, np.int32)
        for j, (_, prompt, toks) in enumerate(part):
            P, m = len(prompt), len(toks)
            seqs[j, :P] = prompt
            seqs[j, P:P + m - 1] = toks[:-1]
            served[j, P - 1:P - 1 + m] = toks
        out.append((seqs, served))
    return out


def compare(cfg: dict, seed: int, traffic: dict, finished,
            control_bits: Optional[int] = None, raw: bool = False) -> dict:
    """The readings of a sample of ``finished``; with ``raw``, also the gap
    of every served token (``gaps``, ``control_gaps``)."""
    from harness.reference import logit_gaps

    chosen = sample(finished, traffic["check_sample"], seed)
    if not chosen:
        return {"sampled": 0}
    gaps, ctl = logit_gaps(cfg, seed, blocks(chosen), control_bits)
    out = {"sampled": len(chosen), "tokens": int(len(gaps))}
    out.update(readings(gaps))
    if ctl is not None:
        out.update({"control_" + k: v for k, v in readings(ctl).items()})
    if raw:
        out.update(gaps=gaps, control_gaps=ctl)
    return out


def readings(gaps: np.ndarray) -> Dict[str, float]:
    """The numbers a limit may hold: the widest gap, the mean gap and the
    mean squared gap over every served token, and (reported only) the share
    that is the reference's best."""
    return {"logit_gap": float(gaps.max()),
            "mean_logit_gap": float(gaps.mean()),
            "mean_sq_logit_gap": float(np.mean(np.square(gaps))),
            "argmax_share": float(np.mean(gaps <= 0.0))}
