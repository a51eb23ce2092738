"""Share (%) of the prefill tokens the engine computed that were padding:
grant-length bucketing and packed-row padding, over the window."""


def read(run):
    c = run.window.counters
    done = c["prefill_tokens"] + c["prefill_pad_tokens"]
    if done <= 0:
        return None
    return 100.0 * c["prefill_pad_tokens"] / done
