"""Median time (ms) from the start of a request's first prefill call to
its first token (``RequestState.t_first - t_first_grant``): every ISO
chunk of the prompt and the steps run between them, over the requests due
in the window that got their first token before the profiler stopped
(bench/engine_spans.due_stamps)."""
from bench import engine_spans, stats


def read(run):
    s = engine_spans.due_stamps(run)
    if not s:
        return None
    return 1e3 * stats.percentile([f - g for _, g, f in s], 50)
