"""Open-loop request generator, driven by a traffic-mix file.

A mix (``bench/traffic/<mix>.json``) states the arrival process and the
length distributions; the cell states the rate.  The schedule is the same
for every seed: the n = round(rate * seconds) prompt lengths, output
lengths and inter-arrival gaps are each the distribution's quantiles at
(i + 0.5) / n (a stratified sample), clipped to the mix's range, and put in
order by shuffles that no seed changes.  The seed draws the token ids (and
the cell's weights), so two seeds differ in content, never in the work or
its timing: with a dozen requests a window, the order of arrivals alone
moved the TTFT median by a third between seeds on the chip.

Mix keys:

    arrivals   {"kind": "poisson"}, or {"kind": "onoff", "on_s": a,
               "off_s": b}: Poisson arrivals inside on-periods only, at
               rate * (a + b) / a, so the mean rate is the cell's
    prompt     a length distribution (below)
    output     a length distribution: tokens to generate
    shared_prefix  optional {"groups": g, "length": <distribution>}: each
               request belongs to one of g groups (round robin before the
               shuffle) and starts with its group's prefix of that length,
               followed by its own ``prompt`` tokens

A length distribution is {"dist": "lognormal", "median": m, "sigma": s,
"min": lo, "max": hi}, {"dist": "uniform", "min": lo, "max": hi} or
{"dist": "fixed", "value": v}.  Every request is greedy and never stops
early (no end-of-sequence token), so it makes exactly its output length.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import Dict, List

import numpy as np

_SEED_MASK = (1 << 64) - 1


@dataclass(frozen=True)
class TrafficRequest:
    index: int                 # position in due order
    due_s: float               # seconds after the window opens
    prompt: np.ndarray         # (P,) int32 token ids
    max_new: int               # tokens to generate


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent generator per (seed, stream); any Python int seed."""
    return np.random.default_rng([seed & _SEED_MASK, stream])


def quantile_lengths(spec: Dict, n: int) -> np.ndarray:
    """The n stratified quantiles of a length distribution, as ints."""
    q = (np.arange(n) + 0.5) / n
    kind = spec["dist"]
    if kind == "fixed":
        vals = np.full(n, float(spec["value"]))
    elif kind == "uniform":
        vals = spec["min"] + q * (spec["max"] - spec["min"])
    elif kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in q])
        vals = spec["median"] * np.exp(spec["sigma"] * z)
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    lo = spec.get("min", 1)
    hi = spec.get("max", math.inf)
    return np.clip(np.rint(vals), lo, hi).astype(np.int64)


def _poisson_gaps(n: int, span_s: float) -> np.ndarray:
    """n exponential quantile gaps scaled to sum to ``span_s``."""
    q = (np.arange(n) + 0.5) / n
    g = -np.log1p(-q)
    return g * (span_s / g.sum())


def arrival_times(arrivals: Dict, n: int, seconds: float,
                  rng: np.random.Generator) -> np.ndarray:
    """Sorted due times in [0, seconds) for n requests."""
    kind = arrivals.get("kind", "poisson")
    if kind == "poisson":
        gaps = rng.permutation(_poisson_gaps(n, seconds))
        return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    if kind == "onoff":
        on, off = float(arrivals["on_s"]), float(arrivals["off_s"])
        on_total = seconds * on / (on + off)
        gaps = rng.permutation(_poisson_gaps(n, on_total))
        t_on = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
        # map on-time to wall time: every full on-period adds an off-period
        return t_on + np.floor(t_on / on) * off
    raise ValueError(f"unknown arrival process {kind!r}")


def generate(mix: Dict, rate: float, seconds: float, seed: int,
             vocab: int) -> List[TrafficRequest]:
    """Requests due in a window of ``seconds`` at ``rate`` requests/s."""
    n = max(1, int(round(rate * seconds)))
    due = arrival_times(mix.get("arrivals", {"kind": "poisson"}), n,
                        seconds, rng_for(0, 0))
    order = rng_for(0, 1).permutation(n)
    own = quantile_lengths(mix["prompt"], n)[order]
    out = quantile_lengths(mix["output"], n)[
        rng_for(0, 2).permutation(n)][order]
    tok_rng = rng_for(seed, 3)
    prefixes, group = [], np.zeros(n, np.int64)
    shared = mix.get("shared_prefix")
    if shared:
        g = int(shared["groups"])
        plen = rng_for(0, 4).permutation(
            quantile_lengths(shared["length"], g))
        prefixes = [tok_rng.integers(0, vocab, int(p)).astype(np.int32)
                    for p in plen]
        group = rng_for(0, 5).permutation(np.arange(n) % g)
    reqs = []
    for i in range(n):
        body = tok_rng.integers(0, vocab, int(own[i])).astype(np.int32)
        prompt = np.concatenate([prefixes[group[i]], body]) if shared \
            else body
        reqs.append(TrafficRequest(index=i, due_s=float(due[i]),
                                   prompt=prompt, max_new=int(out[i])))
    return reqs


def release_page_counts(reqs: List[TrafficRequest], page_size: int
                        ) -> List[int]:
    """Every count of pages a request of these can hold when its pages are
    freed: at most its prompt and all but its last token, fewer when it is
    preempted part way."""
    most = max(len(r.prompt) + r.max_new - 1 for r in reqs)
    return list(range(1, -(-most // page_size) + 1))


def longest_request(mix: Dict) -> int:
    """Most tokens one request of this mix can hold (prompt + output)."""
    def top(spec):
        return spec["value"] if spec["dist"] == "fixed" else spec["max"]
    extra = top(mix["shared_prefix"]["length"]) \
        if mix.get("shared_prefix") else 0
    return int(extra + top(mix["prompt"]) + top(mix["output"]))
