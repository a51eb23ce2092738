"""Model FLOP utilization (%) of the traced window: the model operations of
every real token the traced steps processed (bench/flops: prompt tokens of
each grant, one decoded token per active row, a logits row per sampled
token) over window length x chips x the chip's bf16 peak."""


def read(run):
    t = run.trace
    if t is None or t.window_s <= 0 or not run.traced_steps:
        return None
    return 100.0 * run.traced_model_flops() / (
        t.window_s * run.chips * run.peaks["bf16_flops_per_s"])
