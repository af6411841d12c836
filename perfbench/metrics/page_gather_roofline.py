"""Page-gather attention (``kernels/common.py``): least time of the
attention the window's launches had to run, over the device time of the
``page_gather_attention`` kernel in the trace.  Each chunk launch's rows
come from the request timelines (chunk start and length), each decode
row's context from the tokens the client saw; the least time is
``work.attention_least_s`` at the chip's bf16 peak and HBM bandwidth over
the live bcq4 pages at their logical size.  Moves ``ttft_p95_s``."""
from harness import launches, work


def read(ctx):
    t = ctx["trace"]["kernel_s"].get("page_gather_attention", 0.0)
    if t <= 0:
        return None
    p = ctx["peaks"]
    ps = ctx["cfg"]["engine"]["page_size"]
    least = sum(
        work.attention_least_s(ctx["cfg"], ps, rows, p["bf16_flops_per_s"], p["hbm_bytes_per_s"])
        for rows in launches.attention_rows(ctx)
    )
    return 100.0 * least / t
