"""Share (%) of the device time of prefill calls in which a collective op
runs and no compute op does, from the profiler trace, averaged over the
chips.  Nothing to read on one chip, where prefill has no collective."""


def read(run):
    t = run.trace
    if t is None or t.prefill_s <= 0 or t.prefill_collective_s <= 0:
        return None
    return 100.0 * t.prefill_exposed_collective_s / t.prefill_s
