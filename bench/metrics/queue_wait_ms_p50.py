"""Median queue wait (ms): from the engine's ``add_request`` to the start
of the first prefill call carrying any of the request's tokens
(``RequestState.t_first_grant - t_submit``), over the requests due in the
window that got their first token before the profiler stopped
(bench/engine_spans.due_stamps).  The wait for a free slot and for a grant
under the prefill budget; the wait for the step boundary comes before
``add_request`` and is the harness's handover lateness."""
from bench import engine_spans, stats


def read(run):
    s = engine_spans.due_stamps(run)
    if not s:
        return None
    return 1e3 * stats.percentile([g - sub for sub, g, _ in s], 50)
