"""Whether what the timed path served is correct.

Once the window has closed, a sample of the requests it finished, drawn
from the seed with the longest among them, is run through the plain
float32 reference (``reference/model.py``), each prompt followed by the
tokens that were served for it. For every served token the reference
gives the logits at the position that produced it; the number compared is
the widest gap by which a served token's logit lies below the reference's
best there (``served_gap``). The first token of a request comes from the
batch-1 prefill, the others from decode steps that read the slot cache
after ``insert_slot``, so both paths are held to it. Served tokens are
greedy, so a sound run has gaps only where rounding reorders near ties.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

from reference import model as reference


def draw_sample(done: Sequence, seed: int, size: int) -> List:
    """``size`` finished requests: the longest (prompt and output), and the
    others drawn from ``seed``."""
    ok = [d for d in done if d.tokens]
    if not ok:
        return []
    longest = max(ok, key=lambda d: (len(d.prompt) + len(d.tokens), -d.index))
    rest = [d for d in ok if d is not longest]
    order = np.random.default_rng([seed, 0x5EED]).permutation(len(rest))
    return [longest] + [rest[i] for i in order[:size - 1]]


def sequences(sample: Sequence, cache_len: int) -> Tuple[list, list, np.ndarray]:
    """Each prompt (as the batcher cuts it) with its served tokens; the rows
    whose logits chose them; the served tokens in the same order."""
    seqs, rows, served = [], [], []
    for d in sample:
        prompt = np.asarray(d.prompt[:cache_len], np.int32)
        toks = np.asarray(d.tokens, np.int32)
        seqs.append(np.concatenate([prompt, toks[:-1]]))
        rows.append(np.arange(len(prompt) - 1, len(prompt) - 1 + len(toks)))
        served.append(toks)
    return seqs, rows, np.concatenate(served)


def gaps(logits: np.ndarray, chosen: np.ndarray) -> np.ndarray:
    """How far below each row's best logit the chosen token's lies."""
    return logits.max(-1) - logits[np.arange(len(chosen)), chosen]


def served_gap(model: Dict, seed: int, sample: Sequence, cache_len: int,
               shape: Tuple[int, int, int], control: str = "",
               timings: Dict[str, float] = None) -> Dict[str, float]:
    """``served_gap`` of the sample; with ``control`` (a lower precision),
    also ``control_gap``: the same number for the reference computed in
    that precision put in the program's place (its first choice at each
    position). The reference runs at a fixed ``shape`` (sequences,
    positions, served tokens), so that its programs compile once for every
    run of a cell."""
    seqs, rows, served = sequences(sample, cache_len)
    ref = reference.logits_at(model, seed, seqs, rows, "float32", shape, timings)
    out = {"served_gap": float(gaps(ref, served).max())}
    if control:
        low = reference.logits_at(model, seed, seqs, rows, control, shape, timings)
        out["control_gap"] = float(gaps(ref, low.argmax(-1)).max())
    return out
