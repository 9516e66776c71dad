"""The trace reduction on a small trace made by hand."""
import pytest

import devtrace as dt

# device ops (seconds): busy 1.0-1.5 and 2.0-2.2 (two overlapping ops),
# a kernel of 0.3 s inside the first stretch
OPS = [("fusion.1", 1.0, 1.2), ("paged_attention_tiled.3", 1.2, 1.5),
       ("fusion.2", 2.0, 2.2), ("copy.4", 2.1, 2.15)]
SPANS = [("bench.window", 0.5, 3.0), ("bench.step", 0.9, 2.3),
         ("bench.schedule", 1.6, 1.9)]


def test_busy_union_and_idle_share():
    assert dt.busy_seconds(OPS, 0.5, 3.0) == pytest.approx(0.7)
    r = dt.reduce({"/device:TPU:0": OPS}, SPANS, "paged_attention")
    assert r["window_s"] == pytest.approx(2.5)
    assert 1 - r["busy_s"] / r["window_s"] == pytest.approx(0.72)


def test_kernel_time_and_top_ops():
    s, n = dt.kernel_seconds(OPS, 0.5, 3.0, "paged_attention")
    assert s == pytest.approx(0.3) and n == 1
    top = dt.top_ops(OPS, 0.5, 3.0)
    assert top[0][0] == "paged_attention_tiled.3"
    assert top[0][1] == pytest.approx(0.3)


def test_gaps_are_named_by_the_innermost_host_span():
    gaps = dt.idle_gaps(OPS, SPANS, 0.5, 3.0)
    # 2.2-3.0 (no step open at 2.6: idle), 0.5-1.0 (0.75: idle), 1.5-2.0
    # (1.75: schedule inside step)
    assert [g[0] for g in gaps] == ["idle", "idle", "schedule"]
    assert [round(g[1], 6) for g in gaps] == [0.8, 0.5, 0.5]
    by = dt.idle_by_span(OPS, SPANS, 0.5, 3.0)
    assert by == pytest.approx({"idle": 1.3, "schedule": 0.5})


def test_clip_to_the_window_and_average_over_devices():
    r = dt.reduce({"/device:TPU:0": OPS, "/device:TPU:1": [("f", 0.0, 4.0)]},
                  SPANS, "paged_attention")
    assert r["busy_s"] == pytest.approx((0.7 + 2.5) / 2)


def test_op_names_and_control_flow():
    assert dt.op_name("%paged_attention_tiled.9 = bf16[160] custom-call(x)") \
        == "paged_attention_tiled.9"
    assert dt.op_name("fusion.2") == "fusion.2"
    ops = OPS + [("while.5", 0.9, 2.3)]
    assert "while.5" not in [n for n, _ in dt.top_ops(ops, 0.5, 3.0)]
    assert dt.busy_seconds(ops, 0.5, 3.0) == pytest.approx(1.4)


def test_no_device_and_no_window_are_errors():
    with pytest.raises(RuntimeError):
        dt.reduce({}, SPANS, "paged_attention")
    with pytest.raises(RuntimeError):
        dt.window_of([("bench.step", 0.0, 1.0)])
