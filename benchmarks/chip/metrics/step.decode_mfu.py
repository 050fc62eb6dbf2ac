"""step.decode_mfu (%): model FLOPs of the decode steps in the traced
window over those programs' device time x chips x the chip's bf16 peak.

FLOPs count live rows only: 2 x the weights a token multiplies through,
plus attention over each row's live length, as the member's family counts
them (``decode_step_flops``).  The mean step's FLOPs, from the steps the
host launched in the window, times the decode programs the device ran in
it."""

from harness import trace as T


def read(ctx):
    if ctx.trace is None or not ctx.decode_calls:
        return None
    n_dev = T.count(ctx.trace, "serve_step", modules=True)
    t_dev = T.per_device_time(ctx.trace, "serve_step", modules=True)
    if n_dev <= 0 or t_dev <= 0:
        return None
    work = sum(ctx.family_of(a).decode_step_flops(ctx.models_by_arch[a], lens)
               for a, lens in ctx.decode_calls) / len(ctx.decode_calls)
    return 100.0 * work * n_dev / (t_dev * ctx.chips * ctx.peak["bf16_flops"])
