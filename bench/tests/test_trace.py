"""The trace reduction: busy time is a union, kernels are found by name,
idle gaps are named by the harness span open on the host, and a trace
recorded by the profiler reduces to a window with device work in it."""
import jax
import jax.numpy as jnp
import pytest

from bench import peaks
from bench import trace as bt


def op(a, b, name="fusion.1", detail="", device="/device:TPU:0"):
    return bt.Op(float(a), float(b), name, detail, device)


def test_busy_is_a_union_not_a_sum():
    ops = [op(0, 10), op(5, 15), op(20, 30), op(22, 24)]
    assert bt.busy_ns(ops, 0, 40) == 25.0          # [0,15] + [20,30]
    assert sum(o.end - o.start for o in ops) == 32.0
    assert bt.busy_ns(ops, 8, 21) == 8.0           # clipped to the window


def test_busy_is_averaged_over_devices():
    ops = [op(0, 10, device="/device:TPU:0"),
           op(0, 30, device="/device:TPU:1")]
    assert bt.mean_busy_ns(ops, 0, 40) == 20.0


def test_kernel_events_are_found_by_name():
    # names as a TPU trace gives them: the op's HLO text
    texts = ["%flash_attention_fwd.19 = (bf16[32,9,2048,64]) custom-call("
             "bf16[32,9,2048,64] %q)",
             "%fusion.7 = f32[32,2048] fusion(bf16[32,9,2048,64] "
             "%flash_attention_fwd.19)",
             "%flash_attention_bwd.22 = bf16[32,9,2048,64] custom-call()"]
    ops = [op(a, b, *bt.split_name(t))
           for (a, b), t in zip([(0, 4), (4, 10), (10, 13)], texts)]
    assert [o.base for o in ops] == ["flash_attention_fwd", "fusion",
                                     "flash_attention_bwd"]
    ns, n = bt.kernel_ns(ops, ("flash_attention_fwd", "flash_attention_bwd"),
                         0, 100)
    assert (ns, n) == (7.0, 2)       # the consumer fusion.7 is not counted
    assert bt.kernel_ns(ops, ("fused_apply",), 0, 100) == (0.0, 0)


def test_loops_count_once_and_not_in_the_breakdown():
    ops = [op(0, 100, "while.20", "(s32[]) while(...)"),
           op(10, 40, "flash_attention_fwd.19"),
           op(50, 90, "fusion.3"), op(95, 99, "fusion.4")]
    assert bt.busy_ns(ops, 0, 100) == 100.0
    top = bt.top_ops(ops, 0, 100)
    assert [name for name, _ in top] == ["fusion", "flash_attention_fwd"]
    assert top[0][1] == pytest.approx(44e-9)


def test_idle_gaps_take_the_open_harness_span():
    ops = [op(0, 10), op(30, 40), op(70, 100)]
    spans = [bt.Span(0, 100, bt.WINDOW_SPAN),
             bt.Span(0, 25, "bench.run"),
             bt.Span(25, 60, "bench.sync"),
             bt.Span(60, 100, "bench.run")]
    gaps = bt.idle_gaps(ops, spans, 0, 100)
    # gaps [10,30) under bench.run, [40,70) under bench.sync
    assert gaps == [("bench.sync", pytest.approx(30e-9)),
                    ("bench.run", pytest.approx(20e-9))]
    assert bt.idle_by_span(gaps) == pytest.approx(
        {"bench.sync": 30e-9, "bench.run": 20e-9})


def test_unknown_device_has_no_peak():
    assert peaks.peak("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks.peak("TPU v9 imaginary")


def test_recorded_trace_reduces(tmp_path):
    f = jax.jit(lambda x: jnp.tanh(x @ x))
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation(bt.WINDOW_SPAN):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("bench.run"):
                    f(x).block_until_ready()
    red = bt.reduce_dir(str(tmp_path))
    assert red.window_s > 0
    assert 0 < red.busy_s <= red.window_s
    assert {s.name for s in red.spans} >= {bt.WINDOW_SPAN, "bench.run"}
    b = red.breakdown()
    assert b["device_ops"] and len(b["device_ops"]) <= 10
    assert all(name for name, _ in b["idle_gaps"])
