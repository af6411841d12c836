"""The readers of the engine's tick spans (batch occupancy and step
times) on a synthesized journal: a chunk that finishes a prompt and
blocks, a drained pipeline, spans outside the window, and a journal
from an engine that records no tick spans."""
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from harness import cells, ticks  # noqa: E402

ROWS = 8
# tick -> end of the sync of its decode launch.  Tick 1's sync starts
# before the window; tick 9 launched no decode (the pipeline drained, so
# tick 10's launch had no sync before it); tick 6's chunk finished a
# prompt and blocked, so tick 5's interval holds it (1.20 s) and tick 6's
# is short (0.20 s).
SYNC_END = {1: 10.00, 2: 10.20, 3: 10.35, 4: 10.60, 5: 11.80, 6: 12.00, 7: 12.20,
            8: 12.40, 10: 15.00, 11: 15.30, 12: 15.50, 13: 16.00}
# tick -> (slots, tokens, rows_launched, chunk_bucket, synced)
CHUNKS = {5: (3, 168, 4, 64, False), 6: (1, 20, 1, 32, True), 13: (2, 100, 2, 64, False)}
DECODE = [2, 3, 4, 5, 6, 7, 8, 10, 11, 12, 13]  # ticks with a decode launch
N_ACTIVE = {k: 1 + k % 3 for k in DECODE}


def journal(new_args=True):
    recs = []

    def span(name, t0, t1, args, tid=1):
        recs.append(("span", name, "serving", tid, t0, t1, args, len(recs)))

    for k in range(2, 14):
        t = 10.0 + 0.1 * k
        if new_args:
            span("engine_step", t, t + 0.09, {"tick": k}, tid=0)
            span("admit", t, t + 0.001, {"admitted": 0, "queue": 0}, tid=0)
        if k in CHUNKS:
            slots, tokens, rows, bucket, synced = CHUNKS[k]
            args = {"slots": slots, "tokens": tokens}
            if new_args:
                args.update(tick=k, rows_launched=rows, chunk_bucket=bucket, synced=synced)
            span("prefill_launch", t + 0.002, t + 0.004, args)
        if k in DECODE:
            args = {"n_active": N_ACTIVE[k]}
            if new_args:
                args.update(tick=k, rows_launched=ROWS)
            span("decode_tick", t + 0.005, t + 0.006, args)
    for k, end in SYNC_END.items():
        span("decode_sync", end - 0.02, end, {"tick": k})
    # after the window closes: left out
    span("decode_tick", 20.5, 20.6, {"n_active": 8, "tick": 99, "rows_launched": ROWS})
    span("decode_sync", 20.7, 20.8, {"tick": 14})
    return recs


def ctx(new_args=True):
    return {"open": (10.0, {}), "close": (20.0, {}), "journal": journal(new_args)}


def test_sync_intervals_leave_out_the_drain_and_the_window_edge():
    iv = ticks.sync_intervals(ctx())
    # tick 2's predecessor synced before the window; tick 10's predecessor
    # (9) launched nothing, so no interval spans the drain
    assert sorted(iv) == [3, 4, 5, 6, 7, 8, 11, 12, 13]
    assert iv[5] == pytest.approx(1.20) and iv[6] == pytest.approx(0.20)
    assert ticks.chunk_ticks(ctx()) == set(CHUNKS)


def test_decode_tick_ms():
    # decode-only ticks with a decode-only predecessor: 3, 4, 8, 11, 12
    # (7 follows the chunk tick 6; 10 follows the drain)
    want = sorted([0.15, 0.25, 0.20, 0.30, 0.20])[2]
    assert cells.reader("decode_tick_ms")(ctx()) == pytest.approx(1e3 * want)


def test_chunk_tick_ms_is_a_mean_over_chunk_ticks():
    # ticks 5, 6, 13: the blocking chunk of 6 sits in 5's interval, and
    # the mean over the run 5-6 keeps it
    want = (1.20 + 0.20 + 0.50) / 3
    assert cells.reader("chunk_tick_ms")(ctx()) == pytest.approx(1e3 * want)


def test_occupancy_readers():
    active = sum(N_ACTIVE.values())
    assert cells.reader("decode_row_occupancy")(ctx()) == pytest.approx(
        100.0 * active / (ROWS * len(DECODE)))
    tokens = sum(c[1] for c in CHUNKS.values())
    slots = sum(c[2] * c[3] for c in CHUNKS.values())
    assert tokens == 288 and slots == 416
    assert cells.reader("chunk_token_occupancy")(ctx()) == pytest.approx(100.0 * 288 / 416)


@pytest.mark.parametrize("name", [
    "decode_row_occupancy", "chunk_token_occupancy", "decode_tick_ms", "chunk_tick_ms",
])
def test_readers_return_nothing_without_tick_spans(name):
    """An engine that records only the older launch arguments (no
    ``engine_step``, no ``tick`` or ``rows_launched``): no reading, no
    error.  An empty window reads nothing either."""
    assert cells.reader(name)(ctx(new_args=False)) is None
    empty = ctx()
    empty["open"] = empty["close"] = (30.0, {})
    assert cells.reader(name)(empty) is None


def test_the_cell_reports_the_span_metrics():
    names = [m["name"] for m in cells.cell("gpt3_126m.doc_qa")["per_layer"]]
    for name in ("decode_row_occupancy", "chunk_token_occupancy", "decode_tick_ms",
                 "chunk_tick_ms"):
        assert name in names
