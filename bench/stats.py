"""End-to-end arithmetic over one window's event record.

Times are host ``perf_counter`` seconds.  Each request carries the time it
was due, the time the loop handed it to the engine, and the time of every
token it got: the return of the ``step`` that yielded the token.  Nothing
here reads the engine.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

import numpy as np


@dataclass
class RequestRecord:
    due: float                             # absolute due time
    submitted: Optional[float] = None      # when add_request was called
    token_times: List[float] = field(default_factory=list)
    tokens: List[int] = field(default_factory=list)
    error: str = ""

    @property
    def first(self) -> Optional[float]:
        return self.token_times[0] if self.token_times else None


def percentile(values: Sequence[float], q: float) -> float:
    """q-th percentile (0-100), linear between order statistics."""
    if len(values) == 0:
        raise ValueError("percentile of no values")
    return float(np.percentile(np.asarray(values, np.float64), q))


def ttfts(records: Sequence[RequestRecord], deadline: float) -> List[float]:
    """Time to first token of every request, from when it was due.  A
    request that got no token counts as waiting until ``deadline``: a lower
    bound, so a failure can only raise a percentile."""
    return [(r.first if r.first is not None else deadline) - r.due
            for r in records]


def inter_token_gaps(records: Sequence[RequestRecord], t0: float,
                     t1: float) -> List[float]:
    """Gaps between successive tokens of one request, for every gap that
    ends inside the window [t0, t1]."""
    gaps = []
    for r in records:
        t = r.token_times
        gaps.extend(b - a for a, b in zip(t, t[1:]) if t0 <= b <= t1)
    return gaps


def tokens_in_window(records: Sequence[RequestRecord], t0: float,
                     t1: float) -> int:
    return sum(sum(1 for t in r.token_times if t0 <= t <= t1)
               for r in records)


def end_to_end(records: Sequence[RequestRecord], t0: float, t1: float,
               deadline: float) -> Dict[str, float]:
    """The cell's end-to-end numbers (setup_s aside) over the window."""
    tt = ttfts(records, deadline)
    gaps = inter_token_gaps(records, t0, t1)
    out = {"ttft_p50_ms": 1e3 * percentile(tt, 50),
           "output_tok_per_s": tokens_in_window(records, t0, t1) / (t1 - t0)}
    if gaps:
        out["itl_p99_ms"] = 1e3 * percentile(gaps, 99)
    return out


def lateness(records: Sequence[RequestRecord]) -> Dict[str, float]:
    """How late the loop handed requests to the engine (seconds)."""
    late = [r.submitted - r.due for r in records if r.submitted is not None]
    if not late:
        return {"n": 0}
    return {"n": len(late), "p50_s": percentile(late, 50),
            "p99_s": percentile(late, 99), "max_s": max(late)}
