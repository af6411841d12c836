"""Engine tick loop: mean host time between consecutive decode launches
on quiet ticks, from the engine's ``decode_host_gap_s`` telemetry
(sum / count over the window).  Moves ``ttft_p95_s``."""


def read(ctx):
    a, b = ctx["open"][1], ctx["close"][1]
    n = b["host_gap_count"] - a["host_gap_count"]
    return 1e3 * (b["host_gap_sum"] - a["host_gap_sum"]) / n if n else None
