"""device.idle_outside_step_share (%): share of the traced window in
which no operation ran on the device and no thread was inside a lane
step (``vsr:sched.step``), averaged over the chips used: idle time that
lane hand-off between client threads leaves, not work inside a step.

The reader also prints, on stderr, the first device's idle time split by
the program phase the host was in (``harness.spans.idle_by_phase``)."""

import json
import sys

from harness import spans as S


def read(ctx):
    sl = S.of(ctx)
    if sl is None or not sl.spans:
        return None
    print("idle by program phase (s): "
          + json.dumps(S.idle_by_phase(ctx.trace, sl.spans)),
          file=sys.stderr)
    v = S.idle_outside_step_share(ctx.trace, sl.spans)
    return None if v is None else 100.0 * v
