"""jax.profiler hooks: line device timelines up with the host trace.

``annotate(name)`` wraps the engines' jitted-closure dispatches and, through
``TraceRing.span``, every engine phase span.  While a profiler trace is
active it returns ``jax.profiler.TraceAnnotation`` — the host slice shows up
in the device timeline under the same name as the ring's span.  With no
active trace it returns a shared nullcontext: the hot path pays one
module-global read, nothing else.

``start(dir)`` / ``stop()`` wrap ``jax.profiler.start_trace``/``stop_trace``
(exposed in launch/serve.py as ``--jax-profile DIR``).  ``start`` also opens
one ``engine/clock_anchor`` annotation and keeps the ``perf_counter`` stamp
taken just before it, the way a span stamps its start before its
annotation opens.  The ring stamps ``perf_counter`` seconds while the
profiler counts from its own origin; ``clock_anchor(dir)`` pairs the two, so
``export.chrome_trace(..., anchor=...)`` can put ring events on the
profiler's clock and the two traces overlay.
"""
from __future__ import annotations

import contextlib
import glob
import os
import time
from typing import List, Optional, Tuple

ANCHOR = "engine/clock_anchor"

_active = False
_anchor: Optional[float] = None       # perf_counter stamp of the anchor
_NULL = contextlib.nullcontext()


def profiling_active() -> bool:
    return _active


def annotate(name: str):
    if not _active:
        return _NULL
    import jax
    return jax.profiler.TraceAnnotation(name)


def start(log_dir: str) -> None:
    global _active, _anchor
    import jax
    jax.profiler.start_trace(log_dir)
    _active = True
    _anchor = time.perf_counter()
    with jax.profiler.TraceAnnotation(ANCHOR):
        pass


def stop() -> None:
    global _active
    if not _active:
        return
    import jax
    _active = False
    jax.profiler.stop_trace()


def host_spans(log_dir: str, prefix: str = "engine/"
               ) -> List[Tuple[str, float, float]]:
    """``(name, start_us, dur_us)`` of every host annotation whose name
    starts with ``prefix`` in the newest profile under ``log_dir``, on the
    profiler's clock (microseconds from its origin, as in the profile's own
    ``*.trace.json.gz``), in start order."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    out = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out.extend((e.name, e.start_ns * 1e-3, e.duration_ns * 1e-3)
                       for e in line.events if e.name.startswith(prefix))
    return sorted(out, key=lambda s: s[1])


def clock_anchor(log_dir: str) -> Optional[Tuple[float, float]]:
    """``(perf_counter_s, profiler_us)``: the same instant on both clocks,
    from the anchor the last ``start`` wrote into the profile under
    ``log_dir``.  None if no anchor was recorded or the profile lacks it."""
    if _anchor is None:
        return None
    marks = [s for s in host_spans(log_dir, ANCHOR) if s[0] == ANCHOR]
    if not marks:
        return None
    return _anchor, marks[-1][1]
