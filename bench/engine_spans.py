#!/usr/bin/env python3
"""What the engine's own phase spans and request stamps give the benchmark.

The engine opens a span at every phase of ``PagedEngine.step``
(``engine/step``, ``engine/admit``, ``engine/schedule``, ``engine/pages``,
``engine/inputs``, ``engine/device_wait``, ``engine/logits``,
``engine/sample``, ``engine/release``, beside the ``prefill/...`` and
``decode/...`` dispatch spans), each a profiler annotation while a profile
runs, and stamps each request's first prefill call (``t_first_grant``).

``load`` reads a profile like ``trace_reduce.load`` and keeps the
``engine/`` spans too, so ``trace_reduce.summarize`` names each idle gap by
the innermost engine span; every other number of the summary is the same.
``due_stamps`` gives the stamps of the requests due in a run's window, for
the readers in bench/metrics/.  On a program without the stamps or spans
both find nothing.

    python bench/engine_spans.py <profile dir>

prints the summary of a kept profile (``bench/run.py --keep-trace``) as one
JSON line: window, busy, the idle gaps by span and the share of idle under
a named engine or dispatch span.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path
from typing import List, Tuple

if __name__ == "__main__":
    sys.path[:0] = [str(Path(__file__).resolve().parents[1])]

from bench import trace_reduce  # noqa: E402

ENGINE = "engine/"
ANCHOR = "engine/clock_anchor"
# idle under these is host time inside the engine that has a name; the
# rest is engine/step glue, the harness's own spans and its wait
NAMED = ("prefill dispatch", "decode dispatch")


def load(path: str) -> trace_reduce.Trace:
    from jax.profiler import ProfileData
    tr = trace_reduce.load(path)
    for plane in ProfileData.from_file(path).planes:
        if plane.name != trace_reduce.HOST_PLANE:
            continue
        for line in plane.lines:
            tr.spans.extend(
                trace_reduce.Span(e.name, e.start_ns,
                                  e.start_ns + e.duration_ns)
                for e in line.events
                if e.name.startswith(ENGINE) and e.name != ANCHOR)
    tr.spans.sort(key=lambda s: s.start)
    return tr


def named_idle_share(summary: trace_reduce.Summary) -> float:
    """Share of the window's device idle time that falls under a named
    engine phase span (not ``engine/step`` itself) or a dispatch span."""
    idle = sum(summary.idle_s.values())
    named = sum(v for k, v in summary.idle_s.items()
                if k in NAMED or (k.startswith(ENGINE)
                                  and k != "engine/step"))
    return named / idle if idle > 0 else 0.0


def due_stamps(run) -> List[Tuple[float, float, float]]:
    """``(t_submit, t_first_grant, t_first)`` of each request due in the
    window whose engine state carries all three stamps and which got its
    first token before the profiler stopped.  Stopping the profiler stalls
    the loop for 10-15 s on the chip; the requests due meanwhile are handed
    over together and queue behind each other, a wait the trace makes and
    the program does not.  With one schedule for every seed, much the same
    requests are kept in every run."""
    w = run.window
    traced = run.traced_steps
    stop = max(s.t1 for s in traced) if traced else float("inf")
    out = []
    for i, st in w.states.items():
        rec, g = w.records[i], getattr(st, "t_first_grant", -1.0)
        if w.t0 <= rec.due < w.t1 and g >= 0 and st.t_first >= 0 and \
                rec.first is not None and rec.first <= stop:
            out.append((st.t_submit, g, st.t_first))
    return out


def main(argv=None) -> int:
    args = argv if argv is not None else sys.argv[1:]
    if len(args) != 1:
        print(__doc__, file=sys.stderr)
        return 2
    s = trace_reduce.summarize(load(trace_reduce.find_xspace(args[0])))
    print(json.dumps({"window_s": s.window_s, "busy_s": s.busy_s,
                      "idle_gaps": s.breakdown(top=32)["idle_gaps"],
                      "named_idle_share": named_idle_share(s)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
