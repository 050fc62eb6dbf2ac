"""host.step_self_ms_p50 (ms): the host's own time in a lane step, median
over every lane's steps wholly inside the traced window: a
``vsr:sched.step`` span's length less the time its thread spent blocked
on device results (its ``*.fetch`` spans).  Program spans on the
profiler's clock."""

import statistics

from harness import spans as S


def read(ctx):
    sl = S.of(ctx)
    if sl is None or not sl.spans:
        return None
    vals = S.step_self_ms(sl.spans, sl.window or ctx.trace.window)
    return statistics.median(vals) if vals else None
