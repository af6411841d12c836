"""The engine's tick spans in the measured window, for the per-layer
readers of batch occupancy and step time.

The engine records one ``engine_step`` span per tick, one
``prefill_launch`` per chunk launch and one ``decode_tick`` per decode
launch (each with its ``tick`` and the rows it launched), and one
``decode_sync`` per blocking fetch of a decode launch's tokens (with the
``tick`` of that launch).  An engine without the ``engine_step`` span
records none of these arguments: the readers then return nothing."""
from __future__ import annotations


def spans(ctx, name: str) -> list[tuple[float, float, dict]]:
    """(start, end, args) of the ``name`` spans that start in the window."""
    o, c = ctx["open"][0], ctx["close"][0]
    return [
        (t0, t1, args or {})
        for kind, n, _cat, _tid, t0, t1, args, _seq in ctx["journal"]
        if kind == "span" and n == name and o <= t0 < c
    ]


def has_ticks(ctx) -> bool:
    """Whether the engine recorded its tick spans in the window."""
    return bool(spans(ctx, "engine_step"))


def chunk_ticks(ctx) -> set[int]:
    """The ticks that launched a chunk (any time in the run)."""
    return {
        args["tick"]
        for kind, n, _cat, _tid, _t0, _t1, args, _seq in ctx["journal"]
        if kind == "span" and n == "prefill_launch" and args and "tick" in args
    }


def sync_intervals(ctx) -> dict[int, float]:
    """tick k -> end of the sync of k's decode launch minus end of the
    sync of k-1's, in seconds, for every k where both syncs started in
    the window.

    With two launches in flight and the device busy, consecutive sync
    returns are one device step apart: the interval is the device time of
    tick k's launches (its chunk, if any, and its decode).  A tick with no
    decode launch drains the pipeline, leaving no sync for that tick, so
    no interval spans a drain.  A chunk launch that finishes a prompt
    blocks on its logits before its tick's decode launch goes out; the
    sync of the tick before then returns after that chunk, so that
    chunk's time lands in the previous tick's interval."""
    end = {args["tick"]: t1 for _t0, t1, args in spans(ctx, "decode_sync")}
    return {k: t - end[k - 1] for k, t in end.items() if k - 1 in end}
