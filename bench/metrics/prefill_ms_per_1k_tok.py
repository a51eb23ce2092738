"""Engine prefill time per 1,000 prompt tokens (ms): the engine's fenced
``prefill_s`` over its real (unpadded) ``prefill_tokens`` in the window."""


def read(run):
    c = run.window.counters
    if c["prefill_tokens"] <= 0:
        return None
    return 1e3 * c["prefill_s"] / (c["prefill_tokens"] / 1e3)
