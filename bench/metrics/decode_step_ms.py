"""Engine decode call time (ms): the engine's fenced ``decode_s`` over its
``decode_calls`` in the window."""


def read(run):
    c = run.window.counters
    if c["decode_calls"] <= 0:
        return None
    return 1e3 * c["decode_s"] / c["decode_calls"]
