"""Fused W4A4 linear (``kernels/bcq_linear.py``): least time of the
linears the window's launches had to run, over the device time of the
``bcq_linear`` kernel in the trace.  Launches and their live rows come
from the engine's launch spans (decode ticks: live rows; chunk ticks:
live prompt tokens); the least time is ``work.linear_least_s`` at the
chip's int8 peak and HBM bandwidth.  Moves ``ttft_p95_s``."""
from harness import launches, work


def read(ctx):
    t = ctx["trace"]["kernel_s"].get("bcq_linear", 0.0)
    if t <= 0:
        return None
    p = ctx["peaks"]
    least = sum(
        work.linear_least_s(ctx["cfg"], rows, p["int8_ops_per_s"], p["hbm_bytes_per_s"])
        for rows in launches.linear_rows(ctx)
    )
    return 100.0 * least / t
