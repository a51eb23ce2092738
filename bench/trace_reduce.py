"""Profiler trace -> device busy time, kernel time, idle gaps, exposed
collective time.

The trace is JAX's XSpace (``<dir>/plugins/profile/<run>/*.xplane.pb``),
read with ``jax.profiler.ProfileData``.  Device planes are named
``/device:TPU:<n>``; their ``XLA Ops`` line holds one event per executed HLO
op (a Pallas kernel is an op named after the kernel).  The host plane
``/host:CPU`` holds the ``TraceAnnotation`` spans of the harness
(``harness/...``) and of the engine (``prefill/...``, ``decode/...``) on the
same clock.

Everything below the adapter works on plain intervals ``(start_ns, end_ns)``
so it can be checked on synthetic ones.  The traced window is the span from
the first traced ``harness/step`` to the end of the last one.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
STEP_SPAN = "harness/step"
# host spans an idle gap can be attributed to, innermost first
DISPATCH_PREFIXES = ("prefill/", "decode/")
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all|psum", re.I)


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------

def union(iv: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for a, b in sorted(iv):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def total(iv: Iterable[Interval]) -> float:
    return sum(b - a for a, b in union(iv))


def clip(iv: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def subtract(a: Iterable[Interval], b: Iterable[Interval]) -> List[Interval]:
    """Parts of the union of ``a`` not covered by the union of ``b``."""
    out, bs = [], union(b)
    for lo, hi in union(a):
        cur = lo
        for blo, bhi in bs:
            if bhi <= cur or blo >= hi:
                continue
            if blo > cur:
                out.append((cur, blo))
            cur = max(cur, bhi)
            if cur >= hi:
                break
        if cur < hi:
            out.append((cur, hi))
    return out


def intersect(a: Iterable[Interval], b: Iterable[Interval]) -> List[Interval]:
    ua = union(a)
    return subtract(ua, subtract(ua, b))


def gaps(busy: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return subtract([(lo, hi)], busy)


# ---------------------------------------------------------------------------
# a trace as plain data
# ---------------------------------------------------------------------------

@dataclass
class Op:
    name: str
    start: float
    end: float


@dataclass
class Span:
    name: str
    start: float
    end: float


@dataclass
class Trace:
    ops: Dict[int, List[Op]] = field(default_factory=dict)   # per chip
    spans: List[Span] = field(default_factory=list)          # host


def find_xspace(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str) -> Trace:
    """Read an XSpace file into device ops and host spans."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    tr = Trace()
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                ops.extend(Op(e.name, e.start_ns, e.start_ns + e.duration_ns)
                           for e in line.events)
            tr.ops[int(m.group(1))] = sorted(ops, key=lambda o: o.start)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(("harness/",) + DISPATCH_PREFIXES):
                        tr.spans.append(Span(e.name, e.start_ns,
                                             e.start_ns + e.duration_ns))
    tr.spans.sort(key=lambda s: s.start)
    return tr


# ---------------------------------------------------------------------------
# reduction
# ---------------------------------------------------------------------------

def op_family(name: str) -> str:
    """Group name of an op: the kernel or HLO instruction name without its
    numeric suffix.  TPU traces name an op event by its HLO text
    (``%flash_decode.3 = (f32[...]) custom-call(...)`` -> ``flash_decode``;
    ``fusion.123`` -> ``fusion``)."""
    m = re.match(r"%?([^\s=]+)", name)
    return re.sub(r"(\.\d+)+$", "", m.group(1) if m else name)


# ops that hold other ops of the same line (a scan over layers is one
# ``while`` whose body's ops are events of their own): counted in busy time
# through their children, never as ops of their own
CONTAINERS = ("while", "conditional", "call")


def span_family(name: str) -> str:
    """``prefill/T=512x2`` -> ``prefill``; ``harness/step`` stays."""
    if name.startswith(DISPATCH_PREFIXES):
        return name.split("/", 1)[0] + " dispatch"
    return name


def traced_window(tr: Trace) -> Optional[Interval]:
    steps = [s for s in tr.spans if s.name == STEP_SPAN]
    if not steps:
        return None
    return steps[0].start, max(s.end for s in steps)


def host_segments(spans: Sequence[Span], lo: float, hi: float
                  ) -> List[Tuple[float, float, str]]:
    """[lo, hi) cut into pieces, each named by the innermost host span open
    in it (spans nest or are disjoint, so the innermost is the latest
    opened), or ``harness/wait`` where the host was in none (the loop
    waiting for the next arrival)."""
    cuts = sorted({lo, hi} | {t for s in spans for t in (s.start, s.end)
                              if lo < t < hi})
    by_start = sorted(spans, key=lambda s: s.start)
    out, open_, i = [], [], 0
    for a, b in zip(cuts, cuts[1:]):
        while i < len(by_start) and by_start[i].start <= a:
            open_.append(by_start[i])
            i += 1
        open_ = [s for s in open_ if s.end > a]
        inner = max(open_, key=lambda s: (s.start, -s.end), default=None)
        name = span_family(inner.name) if inner else "harness/wait"
        if out and out[-1][2] == name and out[-1][1] == a:
            out[-1] = (out[-1][0], b, name)
        else:
            out.append((a, b, name))
    return out


def attribute(idle: Sequence[Interval], segments) -> Dict[str, float]:
    """Idle time per host-span name: sorted disjoint idle intervals against
    the sorted host segments, in one pass."""
    out: Dict[str, float] = defaultdict(float)
    j = 0
    for lo, hi in idle:
        while j < len(segments) and segments[j][1] <= lo:
            j += 1
        k = j
        while k < len(segments) and segments[k][0] < hi:
            a, b, name = segments[k]
            d = min(b, hi) - max(a, lo)
            if d > 0:
                out[name] += d
            k += 1
    return out


def dispatch_windows(tr: Trace, prefix: str, hi: float) -> List[Interval]:
    """Device-side extent of each engine call whose dispatch span starts
    with ``prefix``: from its dispatch to the next dispatch of any call
    (the engine waits for every call's outputs before it issues the next),
    or to the end of the enclosing harness step."""
    disp = [s for s in tr.spans if s.name.startswith(DISPATCH_PREFIXES)]
    steps = [s for s in tr.spans if s.name == STEP_SPAN]
    out = []
    for i, s in enumerate(disp):
        if not s.name.startswith(prefix):
            continue
        end = disp[i + 1].start if i + 1 < len(disp) else hi
        step_end = min((st.end for st in steps
                        if st.start <= s.start < st.end), default=hi)
        out.append((s.start, min(end, step_end)))
    return out


@dataclass
class Summary:
    window_s: float
    busy_s: float                           # mean over chips
    op_s: Dict[str, float]                  # op family -> s, mean over chips
    idle_s: Dict[str, float]                # host span family -> s, mean
    prefill_s: float = 0.0                  # device time in prefill calls
    prefill_exposed_collective_s: float = 0.0
    prefill_collective_s: float = 0.0

    def kernel_s(self, *names: str) -> float:
        return sum(self.op_s.get(n, 0.0) for n in names)

    def breakdown(self, top: int = 10) -> Dict[str, list]:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:top]
        idle = sorted(self.idle_s.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in idle]}


def summarize(tr: Trace, window: Optional[Interval] = None) -> Summary:
    """Busy, per-op and idle time in the traced window, averaged over the
    chips in the trace; and, inside prefill calls, how much collective time
    no compute op overlapped."""
    window = window or traced_window(tr)
    if window is None or not tr.ops:
        raise ValueError("trace holds no harness steps or no device ops")
    lo, hi = window
    n = len(tr.ops)
    op_s: Dict[str, float] = defaultdict(float)
    idle_s: Dict[str, float] = defaultdict(float)
    busy = pre = pre_coll = pre_exposed = 0.0
    pre_win = dispatch_windows(tr, "prefill/", hi)
    segments = host_segments(tr.spans, lo, hi)
    for ops in tr.ops.values():
        ivs = clip([(o.start, o.end) for o in ops], lo, hi)
        busy += total(ivs)
        for o in ops:
            d = min(o.end, hi) - max(o.start, lo)
            fam = op_family(o.name)
            if d > 0 and fam not in CONTAINERS:
                op_s[fam] += d
        for name, d in attribute(gaps(ivs, lo, hi), segments).items():
            idle_s[name] += d
        fams = [(op_family(o.name), o) for o in ops]
        coll = [(o.start, o.end) for f, o in fams if COLLECTIVE.search(f)]
        comp = [(o.start, o.end) for f, o in fams
                if not COLLECTIVE.search(f) and f not in CONTAINERS]
        pre += total(intersect(ivs, pre_win))
        in_pre = intersect(clip(coll, lo, hi), pre_win)
        pre_coll += total(in_pre)
        pre_exposed += total(subtract(in_pre, comp))
    ns = 1e-9 / n
    return Summary(window_s=(hi - lo) * 1e-9, busy_s=busy * ns,
                   op_s={k: v * ns for k, v in op_s.items()},
                   idle_s={k: v * ns for k, v in idle_s.items()},
                   prefill_s=pre * ns, prefill_collective_s=pre_coll * ns,
                   prefill_exposed_collective_s=pre_exposed * ns)
