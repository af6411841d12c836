"""Model step, prompt processing (the chunk program, with that tick's
decode launch): mean time of a tick that launched a chunk, read as the
interval between the returns of the syncs of consecutive decode launches
(``harness/ticks.py`` ``sync_intervals``) over ticks k that launched a
chunk and whose tick k-1 was synced too.  A request with n prompt chunks
waits about n times this for its first token.

When a chunk that finishes a prompt blocks, the sync of the tick before
returns after it, so that chunk's time lands in the previous interval:
one interval holds the next tick's chunk and the chunk tick's own is
short by as much.  Over a run of chunk ticks the sum is still right,
which is why this is a mean and not a median.  Moves ``ttft_p95_s``."""
from harness import ticks


def read(ctx):
    if not ticks.has_ticks(ctx):
        return None
    chunk = ticks.chunk_ticks(ctx)
    dt = [d for k, d in ticks.sync_intervals(ctx).items() if k in chunk]
    return 1e3 * sum(dt) / len(dt) if dt else None
