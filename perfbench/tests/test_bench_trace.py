"""The trace reduction on a synthesized trace: busy union, kernel sums,
idle share and the attribution of idle gaps to host spans."""
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from harness import xplane  # noqa: E402

MS = 1_000_000  # ns


def trace():
    # window 0..100 ms; ops overlap at 10..30 and 25..40, a kernel at 50..60
    dev = [[
        ("fusion.1", 10 * MS, 30 * MS),
        ("fusion.2", 25 * MS, 40 * MS),
        ("%bcq_linear.3 = f32[128,768]{1,0:T(8,128)} custom-call(%a, %b)", 50 * MS, 60 * MS),
        ("%while.2 = (s32[], bf16[1,64,768]) while(%t), body=%b.1, bcq_linear", 50 * MS, 58 * MS),
        ("page_gather_attention", 60 * MS, 70 * MS),
        ("bcq_linear.4", 95 * MS, 120 * MS),  # clipped at the window's end
        ("fusion.5", -20 * MS, -10 * MS),  # before the window: ignored
    ]]
    host = [[
        (xplane.WINDOW_SPAN, 0, 100 * MS),
        ("engine.step", 0, 45 * MS),
        ("PjitFunction(fused)", 40 * MS, 44 * MS),
        ("client.wait", 70 * MS, 95 * MS),
    ]]
    return dev, host


def test_busy_union_kernels_and_idle():
    red = xplane.reduce(*trace(), kernels=("bcq_linear", "page_gather_attention"))
    assert red["window_s"] == pytest.approx(0.1)
    # busy: 10..40 (30) + 50..70 (20) + 95..100 (5) = 55 ms
    assert red["busy_s"] == pytest.approx(0.055)
    assert red["kernel_s"]["bcq_linear"] == pytest.approx(0.015)
    assert red["kernel_s"]["page_gather_attention"] == pytest.approx(0.010)
    ops = dict(red["device_ops"])
    assert ops["fusion"] == pytest.approx(0.035)  # 20 + 15, overlap counted per op
    assert ops["bcq_linear f32[128,768]"] == pytest.approx(0.010)
    assert not any(k.startswith("while") for k in ops)  # a container, not an op
    gaps = dict(red["idle_gaps"])
    # gaps 0..10 (engine.step), 40..50 (midpoint 45: engine.step ended, so
    # host idle), 70..95 (client.wait)
    assert gaps["engine.step"] == pytest.approx(0.010)
    assert gaps["host idle"] == pytest.approx(0.010)
    assert gaps["client.wait"] == pytest.approx(0.025)
    assert 1 - red["busy_s"] / red["window_s"] == pytest.approx(0.45)


def test_innermost_host_span_wins():
    dev = [[("fusion", 0, 10 * MS), ("fusion", 20 * MS, 30 * MS)]]
    host = [[
        (xplane.WINDOW_SPAN, 0, 30 * MS),
        ("engine.step", 0, 30 * MS),
        ("PjitFunction(fused)", 12 * MS, 18 * MS),
    ]]
    assert dict(xplane.reduce(dev, host)["idle_gaps"]) == {"PjitFunction(fused)": pytest.approx(0.01)}


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError):
        xplane.reduce([[("fusion", 0, 1)]], [[("engine.step", 0, 5)]])


def test_two_chips_average():
    dev, host = trace()
    red = xplane.reduce(dev + [[("fusion", 0, 100 * MS)]], host)
    assert red["busy_s"] == pytest.approx((0.055 + 0.1) / 2)
    assert red["n_device_lines"] == 2
