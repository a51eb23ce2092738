"""Collective device time in prefill calls per 1,000 prompt tokens (ms):
the trace's collective op time inside prefill dispatch windows, averaged
over the chips (``Summary.prefill_collective_s``), over the real prompt
tokens of the traced steps' grants.  Whether a change moved the
collectives themselves or only the compute that may hide them, which
``exposed_collective_share`` cannot tell apart.  Nothing to read on one
chip, where prefill has no collective."""


def read(run):
    t = run.trace
    tokens = sum(n for s in run.traced_steps for _, n, _ in s.grants)
    if t is None or tokens <= 0 or t.prefill_collective_s <= 0:
        return None
    return 1e3 * t.prefill_collective_s / (tokens / 1e3)
