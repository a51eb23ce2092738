"""Share (%) of the roofline reached by the paged prefill kernel
(``flash_prefill_paged``, resumed grants) in the traced steps: the least
time its bytes and operations allow (bench/flops.flash_prefill_paged_work)
over its device time in the trace."""
from bench import flops


def read(run):
    t = run.trace
    grants = [(a, n) for s in run.traced_steps for a, n, _ in s.grants
              if a > 0]
    if t is None or not grants:
        return None
    k = t.kernel_s("flash_prefill_paged")
    if k <= 0:
        return None
    return 100.0 * run.least_seconds(
        flops.flash_prefill_paged_work(run.shape, grants)) / k
