"""Chunk batch (``serving/engine.py`` ``_prefill_tick_all``): share of
the token positions of the window's chunk launches that held a prompt
token, 100 × Σ ``tokens`` / Σ (``rows_launched`` × ``chunk_bucket``)
over the ``prefill_launch`` spans (the batch is padded to a power of
two, a ragged last chunk to a power-of-two length).  Moves
``ttft_p95_s``."""
from harness import ticks


def read(ctx):
    args = [a for _, _, a in ticks.spans(ctx, "prefill_launch") if "rows_launched" in a]
    slots = sum(a["rows_launched"] * a["chunk_bucket"] for a in args)
    return 100.0 * sum(a["tokens"] for a in args) / slots if slots else None
