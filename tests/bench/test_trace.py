"""The benchmark's trace reduction (bench/trace.py) on small traces: a
recorded one (host spans, no device plane on the CPU) and hand-made device
lines."""
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[2] / "bench"
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402

tracing = harness.tracing


def _events():
    # device: two overlapping ops, then a gap, then a kernel; host spans
    ops = {"/device:TPU:0": [("fusion.1", 100, 300), ("fusion.2", 200, 400),
                             ("jit_fused_restore_pallas", 700, 900)]}
    mods = {"/device:TPU:0": [("jit_step(1)", 100, 400),
                              ("jit_fused_restore_pallas(2)", 700, 900)]}
    spans = [("bench.traced", 0, 1000), ("bench.restore", 0, 600),
             ("bench.first_token", 600, 1000)]
    return tracing.Events(ops, mods, spans)


def test_busy_is_the_union_of_operations():
    red = tracing.reduce(_events(), 0, 1000)
    assert red.window_s == pytest.approx(1000e-9)
    assert red.busy_s == pytest.approx(500e-9)           # [100,400] + [700,900]
    assert red.idle_share == pytest.approx(0.5)


def test_per_operation_and_program_sums():
    red = tracing.reduce(_events(), 0, 1000)
    ops = dict(red.ops)
    assert ops["fusion.1"] == pytest.approx(200e-9)
    assert ops["fusion.2"] == pytest.approx(200e-9)
    assert red.module_s("jit_fused_restore_pallas") == pytest.approx(200e-9)
    assert red.module_s("jit_step") == pytest.approx(300e-9)


def test_gaps_are_labelled_by_the_innermost_span():
    red = tracing.reduce(_events(), 0, 1000)
    assert red.gaps[0] == ("bench.restore", pytest.approx(300e-9))    # 400..700
    assert ("bench.first_token", pytest.approx(100e-9)) in red.gaps    # 900..1000
    assert ("bench.restore", pytest.approx(100e-9)) in red.gaps        # 0..100
    bd = tracing.breakdown(red, top=2)
    assert len(bd["idle_gaps"]) == 2 and bd["device_ops"][0][0].startswith("fusion")


def test_window_clips_events():
    red = tracing.reduce(_events(), 250, 800)
    assert red.busy_s == pytest.approx((400 - 250 + 800 - 700) * 1e-9)


def test_busy_time_is_averaged_over_devices():
    ev = _events()
    ev.ops["/device:TPU:1"] = [("x", 0, 1000)]
    red = tracing.reduce(ev, 0, 1000)
    assert red.busy_s == pytest.approx((500e-9 + 1000e-9) / 2)


def test_an_empty_window_is_refused():
    with pytest.raises(ValueError, match="empty window"):
        tracing.reduce(_events(), 5, 5)


def test_reduce_refuses_more_busy_time_than_window(monkeypatch):
    monkeypatch.setattr(tracing, "union", lambda iv: [(0, 2000)])
    with pytest.raises(ValueError, match="exceeds the window"):
        tracing.reduce(_events(), 0, 1000)


def test_a_trace_without_device_operations_is_refused():
    with pytest.raises(ValueError, match="no device operations"):
        tracing.reduce(tracing.Events({}, {}, []), 0, 10)


def test_recorded_trace_holds_the_bench_spans(tmp_path):
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    with tracing.record(tmp_path):
        with TraceAnnotation("bench.traced"):
            with TraceAnnotation("bench.restore"):
                jnp.arange(8.0).sum().block_until_ready()
        with TraceAnnotation("not.bench"):
            pass
    ev = tracing.load(tmp_path)
    names = [n for n, _, _ in ev.spans]
    assert names.count("bench.traced") == 1 and "bench.restore" in names
    assert "not.bench" not in names
    a, b = tracing.window(ev, "bench.traced")
    (_, ra, rb), = [s for s in ev.spans if s[0] == "bench.restore"]
    assert a <= ra <= rb <= b
    with pytest.raises(ValueError):
        tracing.window(ev, "bench.absent")
