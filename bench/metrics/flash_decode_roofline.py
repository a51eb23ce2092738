"""Share (%) of the roofline reached by the paged decode kernels
(``flash_decode`` and its split-KV ``flash_decode_reduce``) in the traced
steps: the least time their bytes and operations allow
(bench/flops.flash_decode_work; memory bound at these shapes) over their
device time in the trace."""
from bench import flops


def read(run):
    t = run.trace
    steps = [s for s in run.traced_steps if s.decode_resident]
    if t is None or not steps:
        return None
    k = t.kernel_s("flash_decode", "flash_decode_reduce")
    if k <= 0:
        return None
    f = b = 0
    for s in steps:
        df, db = flops.flash_decode_work(run.shape, s.decode_resident)
        f, b = f + df, b + db
    return 100.0 * run.least_seconds((f, b)) / k
