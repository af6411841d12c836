"""Model step, token generation (the decode program): median time of a
decode-only tick, read as the interval between the returns of the syncs
of consecutive decode launches (``harness/ticks.py`` ``sync_intervals``)
over ticks k where neither k nor k-1 launched a chunk.  A chunk of
tick k+1 that finishes a prompt blocks and lands in tick k's interval;
a few such intervals barely move the median.  Moves ``ttft_p95_s``."""
import numpy as np

from harness import ticks


def read(ctx):
    if not ticks.has_ticks(ctx):
        return None
    chunk = ticks.chunk_ticks(ctx)
    dt = [d for k, d in ticks.sync_intervals(ctx).items() if k not in chunk and k - 1 not in chunk]
    return 1e3 * float(np.median(dt)) if dt else None
