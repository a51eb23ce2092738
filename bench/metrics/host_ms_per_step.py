"""Engine host time per step (ms): the harness's clock around ``step()``
minus the engine's own fenced device-call time (``prefill_s + decode_s``),
averaged over the steps of the window.  What is left is scheduling,
admission, page bookkeeping, input building and host sampling."""


def read(run):
    steps = run.window_steps
    if not steps:
        return None
    host = sum((s.t1 - s.t0) - s.prefill_s - s.decode_s for s in steps)
    return 1e3 * host / len(steps)
