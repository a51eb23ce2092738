"""Structured trace events: a bounded ring the serving stack narrates into.

Every scheduler decision, allocator mutation and engine phase call appends one
``TraceEvent`` — cheap enough to leave on in production (one dataclass per
event; the ring drops the oldest events past ``capacity`` and counts what it
dropped, so memory is bounded no matter how long the engine runs).

Event vocabulary (payload keys in parentheses; -1 rid/slot = not applicable):

  scheduler   ``grant``   (start, n, padded, last)      one per prefill grant
              ``pack``    (rows, padded)                one per multi-row pack
              ``defer``   ()                            packmate-sharing defer
  allocator   ``alloc``   (n, free, used)               pages from free list
              ``free``    (n, free, used)               pages released
              ``rc_drop`` (n)                           sharer refcount drops
                                                        (no physical release)
              ``cow``     (old, new)                    copy-on-write copy
              ``adopt``   (n_pages, tokens)             prefix-share adoption
  engine      ``admit``   ()                            request -> slot
              ``grant_commit`` (start, n, last)        grant actually ran
              ``prefill_call`` (tokens, pad, rows, calls...)  span, dur > 0
              ``decode_call``  (k, active)              span, dur > 0
              ``sample``  (first, ttft?)                prefill-final sample
              ``accept``  (n, spec)                     tokens committed/slot
              ``spec_rollback`` (n)                     positions invalidated
              ``evict``   ()                            preemption victim
              ``finish``  ()                            request completed
              ``pool``    (used, free, frag)            per-step occupancy
  disagg      ``detach``  ()                            request exported out
              ``attach``  ()                            request imported in
              ``migrate`` (n, rids, us)                 one per PageTransfer,
                                                        n = distinct pages
  cost model  ``decision`` (point, chosen, static, ...) model-driven choice
              ``warning``  (what, reason, path)         degradation notice
  phases      ``engine/step``     ()                    span: all of step()
              ``engine/admit``    ()                    span: admission
              ``engine/schedule`` (grants?)             span: grants, packing
              ``engine/pages``    ()                    span: page growth, CoW
              ``engine/inputs``   ()                    span: host input arrays
              ``prefill/T=...``, ``decode/K=.../S=...`` span: call dispatch
              ``engine/device_wait`` ()                 span: output fence
              ``engine/logits``   ()                    span: logits to host
              ``engine/sample``   ()                    span: sample, commit
              ``engine/release``  (n_pages)             span: page release

The phase spans come from ``TraceRing.span``: each is also a
``jax.profiler.TraceAnnotation`` of the same name while a profile runs
(obs/jaxprof.py), and adds its SELF time (its duration less that of the spans
opened inside it) to the engine counter it is given, so the counters of one
step sum to the step's duration however the spans nest.

``decision`` records every choice the measured cost model
(perf/costmodel.py) made instead of a static default — ``point`` is one of
``kv_splits``/``grant_cap``/``pack_rows``/``spec_gate``, ``chosen`` the
model's answer, ``static`` what the constant would have done, plus the
decision's inputs (depth, k, padded, expected_accept).  ``warning`` is
emitted exactly once per failed cost-table load (missing / malformed /
platform-mismatch) before falling back to static defaults.  Both are
bookkeeping-neutral: ``replay.replay_counters`` ignores kinds outside its
counter vocabulary, and the Chrome-trace exporter renders any unknown kind
as an instant.

``replay.replay_counters`` reconstructs the engine's counters from exactly
this vocabulary — the conservation tests pin that the narration is complete.
Timestamps are ``time.perf_counter()`` seconds (monotonic); spans carry their
START time plus ``dur`` so the Chrome-trace exporter can emit real slices.
"""
from __future__ import annotations

from collections import deque
from dataclasses import dataclass, field
from time import perf_counter
from typing import Any, Dict, List, Optional

from repro.obs import jaxprof


@dataclass
class TraceEvent:
    ts: float                          # perf_counter seconds (monotonic)
    kind: str
    rid: int = -1                      # request id, -1 when not applicable
    slot: int = -1                     # engine slot, -1 when not applicable
    dur: float = 0.0                   # span duration (0 = instant)
    payload: Dict[str, Any] = field(default_factory=dict)


class Span:
    """One timed phase (``TraceRing.span``).  After exit ``t0``/``t1`` hold
    its perf_counter bounds; ``payload`` may be filled while it is open."""
    __slots__ = ("ring", "name", "counter", "payload", "t0", "t1", "child",
                 "_ann")

    def __init__(self, ring: "TraceRing", name: str, counter, payload):
        self.ring, self.name, self.counter = ring, name, counter
        self.payload = payload
        self.child = 0.0
        self._ann = None

    def __enter__(self) -> "Span":
        self.ring._open.append(self)
        self.t0 = perf_counter()
        if jaxprof._active:
            # stamp first, then open: the order jaxprof.start's anchor uses
            self._ann = jaxprof.annotate(self.name)
            self._ann.__enter__()
        return self

    def __exit__(self, *exc) -> None:
        if self._ann is not None:
            self._ann.__exit__(*exc)
        self.t1 = t1 = perf_counter()
        dur = t1 - self.t0
        ring = self.ring
        ring._open.pop()
        if ring._open:
            ring._open[-1].child += dur
        if self.counter is not None:
            self.counter.value += dur - self.child
        if ring.enabled:
            ring._append(TraceEvent(self.t0, self.name, -1, -1, dur,
                                    self.payload))


class TraceRing:
    """Bounded event buffer.  ``enabled=False`` turns ``emit`` into a no-op
    (the obs-off configuration the overhead benchmark compares against)."""

    def __init__(self, capacity: int = 65536, enabled: bool = True):
        assert capacity > 0
        self.capacity = capacity
        self.enabled = enabled
        self.dropped = 0
        self._buf: deque = deque(maxlen=capacity)
        self._open: List[Span] = []       # spans open now, innermost last

    def span(self, name: str, counter=None, **payload) -> Span:
        """Context manager timing one phase under ``name`` into three sinks:
        a ring event (``ts`` = start, ``dur``, ``payload``) when the ring is
        enabled; a profiler annotation of the same name when a profile runs
        (``jaxprof.annotate``); and its self time — duration less the spans
        opened inside it — added to ``counter`` (a registry ``Counter``)."""
        return Span(self, name, counter, payload)

    def emit(self, kind: str, rid: int = -1, slot: int = -1, dur: float = 0.0,
             ts: Optional[float] = None, **payload) -> None:
        if not self.enabled:
            return
        self._append(TraceEvent(
            ts=perf_counter() if ts is None else ts,
            kind=kind, rid=rid, slot=slot, dur=dur, payload=payload))

    def _append(self, ev: TraceEvent) -> None:
        if len(self._buf) == self.capacity:
            self.dropped += 1
        self._buf.append(ev)

    def events(self) -> List[TraceEvent]:
        """Insertion order (oldest surviving event first)."""
        return list(self._buf)

    def clear(self) -> None:
        self._buf.clear()
        self.dropped = 0

    def __len__(self) -> int:
        return len(self._buf)
