"""What decides ``correct``: served tokens against the plain reference.

After the window, a sample of the requests the engine finished, drawn from
the seed and always holding the longest of them, is run through the
configuration's float32 reference (bench/reference/), once per request over
its prompt followed by the tokens it was served.  Every served token was
greedy, so the reference's logit for it should be at or near the
reference's best at that position.  The number compared is the widest gap
``max(ref) - ref[served]`` over all sampled tokens, against the cell's
limit (``correct.logit_gap_max``).  The sample covers every path the
window used: fresh and resumed ISO-chunked prefill grants, packed rows,
decode with and without split-KV, and at TP > 1 the sharded path.

The control puts the reference at float8 (e4m3) in the program's place:
at each position of the same tokens, the gap of the token the float8
reference puts first.  It must fail the limit (bench/control.py).
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

from bench import cells as cells_lib
from bench.traffic import rng_for


def sample(lengths: Dict[int, int], served: Dict[int, int], seed: int,
           max_requests: int, min_tokens: int) -> List[int]:
    """Request indices to check: the longest finished request, then others
    in a seeded order until ``min_tokens`` served tokens or
    ``max_requests`` requests."""
    if not lengths:
        return []
    longest = max(lengths, key=lambda i: (lengths[i], -i))
    rest = [i for i in sorted(lengths) if i != longest]
    order = [longest] + [rest[j] for j in
                         rng_for(seed, 7).permutation(len(rest))]
    out, tokens = [], 0
    for i in order:
        if len(out) >= max_requests or tokens >= min_tokens:
            break
        out.append(i)
        tokens += served[i]
    return out


def served_gaps(ref, weights, config: dict, prompt: np.ndarray,
                served: Sequence[int], precision: str = "fp32"
                ) -> np.ndarray:
    """Per served token, how far the float32 reference's logit for the
    token the program served (``precision="fp32"``), or for the token a
    reference at ``precision`` puts first, lies below the float32
    reference's best."""
    served = np.asarray(served, np.int64)
    tokens = np.concatenate([np.asarray(prompt, np.int32),
                             served[:-1].astype(np.int32)])
    rows = np.arange(len(prompt) - 1, len(tokens))
    ref32 = ref.logits(weights, config, tokens, rows, "fp32")
    pick = served if precision == "fp32" else \
        ref.logits(weights, config, tokens, rows, precision).argmax(-1)
    return ref32.max(-1) - ref32[np.arange(len(rows)), pick]


def check(cell, weights, reqs, done: Dict[int, List[int]],
          seed: int) -> Dict[str, dict]:
    """Each number compared, with its limit."""
    spec = cell.workload["correct"]
    lengths = {i: len(reqs[i].prompt) + reqs[i].max_new for i in done}
    served = {i: len(done[i]) for i in done}
    picked = sample(lengths, served, seed, spec["max_requests"],
                    spec["min_tokens"])
    ref = cells_lib.reference(cell)
    gap, short = 0.0, 0
    for i in picked:
        short += int(len(done[i]) != reqs[i].max_new)
        if done[i]:
            gap = max(gap, float(served_gaps(ref, weights, cell.config,
                                             reqs[i].prompt, done[i]).max()))
    numbers = {
        "logit_gap_max": {"value": gap, "limit": spec["logit_gap_max"]},
        "sampled_requests_short": {"value": short, "limit": 0},
        "sampled_tokens_short_of": {"value": max(
            0, spec["min_tokens"] - sum(served[i] for i in picked)),
            "limit": 0},
    }
    return numbers
