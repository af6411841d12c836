"""The roofline and MFU arithmetic on hand-checked shapes, and the
per-layer readers on a synthesized window."""
import json
import os
import sys
import types

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from harness import cells, work  # noqa: E402

GPT3 = json.load(open(os.path.join(BENCH, "configs", "gpt3_126m.json")))
SC2 = json.load(open(os.path.join(BENCH, "configs", "starcoder2_3b.json")))
V5E = json.load(open(os.path.join(BENCH, "peaks.json")))["devices"]["TPU v5 lite"]
INT8, BF16, BW = V5E["int8_ops_per_s"], V5E["bf16_flops_per_s"], V5E["hbm_bytes_per_s"]


def test_linear_shapes():
    assert work.linears(GPT3) == [(768, 768)] * 4 + [(768, 3072), (3072, 768)]
    assert sum(k * n for k, n in work.linears(GPT3)) == 7_077_888
    # GQA: 24 query heads and 2 KV heads of 128
    assert work.linears(SC2)[:4] == [(3072, 3072), (3072, 256), (3072, 256), (3072, 3072)]


def test_one_row_decode_is_bound_by_packed_weights():
    # per layer: 0.578125 bytes per weight + 2 bytes per activation in and out
    per_layer = 0.578125 * 7_077_888 + 2 * (4 * 1536 + 2 * 3840)
    assert per_layer == 4_119_552
    assert work.linear_least_s(GPT3, 1, INT8, BW) == pytest.approx(12 * per_layer / BW)


def test_large_chunk_is_compute_bound_where_k_n_is_wide():
    rows = 4096
    t = work.linear_least_s(GPT3, rows, INT8, BW)
    by_hand = 0.0
    for k, n in work.linears(GPT3):
        ops = 2 * rows * k * n
        byt = 0.578125 * k * n + 2 * rows * (k + n)
        by_hand += max(ops / INT8, byt / BW)
    assert t == pytest.approx(12 * by_hand)
    # 768x3072: K·N/(K+N) = 614 > the int8 ridge of 480 ops/byte
    assert 2 * rows * 768 * 3072 / INT8 > (0.578125 * 768 * 3072 + 2 * rows * 3840) / BW


def test_attention_work_by_hand():
    ops, byt = work.attention_work(GPT3, 16, n_past=100, n_q=1)
    assert ops == 4 * 12 * 64 * 101
    assert byt == 7 * 16 * 888 + 12 * 64 * 6  # 7 live pages, q in bf16, out in f32
    # a 64-token chunk after 64 cached tokens: Σ visible = 64·64 + 64·65/2
    ops, _ = work.attention_work(SC2, 16, n_past=64, n_q=64)
    assert ops == 4 * 24 * 128 * (64 * 64 + 64 * 65 / 2)
    assert work.kv_token_bytes(SC2) == 2 * 2 * (64 + 8 + 2)


def test_token_flops_by_hand():
    head = 768 * 50432
    want = 2 * (12 * 7_077_888 + head) + 12 * 4 * 12 * 64 * 1
    assert work.token_flops(GPT3, 0, True) == want
    assert work.token_flops(GPT3, 9, False) == 2 * 12 * 7_077_888 + 12 * 4 * 12 * 64 * 10


def ctx(kernel_s, window_s=1.0):
    tracked = [types.SimpleNamespace(
        arrival=types.SimpleNamespace(rid=0, prompt=[0] * 100), times=[0.5, 1.5, 2.5],
    )]
    timeline = types.SimpleNamespace(rid=0, chunks=[(0.2, 64), (1.6, 36)])
    return {
        "cfg": GPT3, "peaks": V5E,
        "trace": {"kernel_s": kernel_s, "window_s": window_s, "busy_s": 0.25 * window_s},
        "run": types.SimpleNamespace(tracked=tracked),
        "open": (1.0, {"host_gap_sum": 1.0, "host_gap_count": 10}),
        "close": (3.0, {"host_gap_sum": 1.5, "host_gap_count": 20}),
        "journal": [
            ("span", "decode_tick", "serving", 1, 2.0, 2.01, {"n_active": 1}, 0),
            ("span", "prefill_launch", "serving", 1, 1.55, 1.6, {"slots": 1, "tokens": 36}, 1),
            ("span", "decode_tick", "serving", 1, 0.9, 0.95, {"n_active": 1}, 2),  # before the window
        ],
        "timelines": [timeline],
    }


def test_readers_on_a_synthesized_window():
    least_lin = work.linear_least_s(GPT3, 1, INT8, BW) + work.linear_least_s(GPT3, 36, INT8, BW)
    c = ctx({"bcq_linear": 4 * least_lin, "page_gather_attention": 1.0})
    assert cells.reader("bcq_linear_roofline")(c) == pytest.approx(25.0)
    # the chunk launch at 1.6 s: 36 tokens after 64; the token seen at 1.5 s
    # (output index 1) decoded at context 100; the one at 2.5 s at 101
    attn = work.attention_least_s(GPT3, 16, [(64, 36)], BF16, BW)
    attn += sum(work.attention_least_s(GPT3, 16, [(n, 1)], BF16, BW) for n in (100, 101))
    assert cells.reader("page_gather_roofline")(c) == pytest.approx(100.0 * attn)
    flops = sum(work.token_flops(GPT3, 64 + j, 64 + j == 99) for j in range(36))
    flops += work.token_flops(GPT3, 100, True) + work.token_flops(GPT3, 101, True)
    assert cells.reader("step_mfu")(c) == pytest.approx(100.0 * flops / INT8)
    assert cells.reader("host_gap_ms")(c) == pytest.approx(50.0)
    assert cells.reader("device_idle_share")(c) == pytest.approx(75.0)


def test_readers_return_nothing_without_a_kernel():
    c = ctx({"bcq_linear": 0.0, "page_gather_attention": 0.0})
    assert cells.reader("bcq_linear_roofline")(c) is None
    assert cells.reader("page_gather_roofline")(c) is None
    c["open"] = c["close"]
    assert cells.reader("host_gap_ms")(c) is None


def test_queue_wait_reader():
    tl = types.SimpleNamespace(t_submit=1.0, t_enqueued=1.0, admits=[1.25], chunks=[])
    tl2 = types.SimpleNamespace(t_submit=0.0, t_enqueued=0.0, admits=[0.5], chunks=[])  # before the window
    c = ctx({})
    c["timelines"] = [tl, tl2]
    assert cells.reader("queue_wait_p95_s")(c) == pytest.approx(0.25)
