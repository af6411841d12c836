"""Engine admission: 95th percentile of queue wait, from the request
timelines: each admission in the window minus the (re)enqueue before it
(``serving/telemetry.py`` ``RequestTimeline``).  Moves ``ttft_p95_s``."""
import numpy as np


def read(ctx):
    o, c = ctx["open"][0], ctx["close"][0]
    waits = []
    for tl in ctx["timelines"]:
        if not tl.admits:
            continue
        enq = [tl.t_submit] + [tl.t_enqueued] * (len(tl.admits) - 1)
        waits += [a - e for a, e in zip(tl.admits, enq) if o <= a < c]
    return float(np.percentile(waits, 95)) if waits else None
