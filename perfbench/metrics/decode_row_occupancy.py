"""Decode batch (``serving/engine.py`` ``_launch_decode``): share of the
rows packed into the window's decode launches that held a decoding
request, 100 × Σ ``n_active`` / Σ ``rows_launched`` over the
``decode_tick`` spans.  Every launch packs all ``n_slots`` rows, so a
light load runs mostly padding.  Moves ``ttft_p95_s``."""
from harness import ticks


def read(ctx):
    args = [a for _, _, a in ticks.spans(ctx, "decode_tick") if "rows_launched" in a]
    launched = sum(a["rows_launched"] for a in args)
    return 100.0 * sum(a["n_active"] for a in args) / launched if launched else None
