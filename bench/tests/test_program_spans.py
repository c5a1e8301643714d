"""The program's spans joined to a traced window: the clock join, set-up
phases, device idle time split by overlap with the host's phases, a
recorded trace, and a whole traced run that reports every metric that
reads them."""
import sys

import jax
import jax.numpy as jnp
import pytest

from bench import program_spans as ps
from bench import trace as bt
from bench.tests import helpers

T = 1_790_000_000_000_000_000   # the program's clock at the profile's start
                                # (time.time_ns(); trace time 50 there)
SPAN_METRICS = ("train.setup_init_s", "train.setup_compile_s",
                "train.setup_oom_compile_s", "train.setup_curvature_s",
                "train.input_idle", "train.control_idle")


def rec(i, name, t0, t1, outcome="ok", parent=None, **attrs):
    from repro.obs import Record
    return Record(i, parent, name, T + t0, T + t1, attrs, outcome)


def synthetic():
    """A run on the program's clock and its trace on the trace's clock
    (program time t at trace time t + 50): set-up, then two chunks, each
    ``bench.run`` opening 1-3 ns before its ``train.run``."""
    recs = [rec(1, "train.init", 0, 100),
            rec(2, "train.compile", 110, 150, outcome="oom", rung=4),
            rec(3, "train.compile", 150, 180, rung=2),
            rec(4, "train.run", 200, 300),
            rec(5, "train.curvature", 310, 330, step=3),
            rec(6, "train.run", 1000, 1900),
            rec(7, "train.data", 1100, 1200, parent=6, rung=2, step=3),
            rec(8, "train.run", 2000, 2900),
            rec(9, "train.control", 2400, 2450, parent=8, step=20)]
    spans = [bt.Span(1000, 3000, bt.WINDOW_SPAN),
             bt.Span(1047, 1954, "bench.run"),
             bt.Span(2049, 2952, "bench.run")]
    # device idle in [1099, 1199) (half under train.data, at 1149-1249 on
    # the trace's clock), [2449, 2469) (under train.control) and
    # [2700, 2800) (under no phase)
    ops = [bt.Op(a, b, "fusion.1", "", "/device:TPU:0")
           for a, b in [(1000, 1099), (1199, 2449), (2469, 2700),
                        (2800, 3000)]]
    ops.append(bt.Op(1000, 3000, "fusion.2", "", "/device:TPU:1"))
    red = bt.Reduced(window_s=2000e-9, busy_s=0.0, ops=ops, spans=spans,
                     lo=1000.0, hi=3000.0)
    return recs, red


def test_join_and_overlap_split(monkeypatch):
    recs, red = synthetic()
    j = ps.join(recs, red.spans)
    # the earliest-opening train.run (3 ns after its bench.run) sits at
    # its bench.run's start: 1 ns early, inside the residual
    assert j.offset_ns == 49 - T
    assert j.residual_ns == 3
    assert [r.id for r in j.setup] == [1, 2, 3, 4, 5]
    assert ps.idle_intervals(red.ops, red.lo, red.hi) == [
        (1099, 1199), (2449, 2469), (2700, 2800)]
    assert ps.overlap_ns([(0, 10), (20, 30)], [(5, 25)]) == 10

    monkeypatch.setattr(ps, "records", lambda: recs)
    ctx = {"trace": red}
    assert ps.setup_seconds(ctx, "train.init") == pytest.approx(100e-9)
    assert ps.setup_seconds(ctx, "train.compile") == pytest.approx(70e-9)
    assert ps.setup_seconds(ctx, "train.compile", "oom") == pytest.approx(
        40e-9)
    assert ps.setup_seconds(ctx, "train.curvature") == pytest.approx(20e-9)
    # 50 of the 100 ns gap under train.data, of a 2000 ns window
    assert ps.idle_share(ctx, ("train.data",)) == pytest.approx(2.5)
    assert ps.idle_share(ctx, ("train.control", "train.curvature")) == \
        pytest.approx(1.0)
    assert ps.idle_share(ctx, ("train.step",)) == 0.0

    # a train.run that outlasts its chunk: the clocks do not join
    late = recs[:-2] + [rec(8, "train.run", 2000, 2910)]
    with pytest.raises(ValueError, match="do not join"):
        ps.join(late, red.spans)
    with pytest.raises(ValueError):
        ps.join(recs[:5], red.spans)       # chunks with no program run


def test_readers_give_nothing_without_the_programs_spans(monkeypatch):
    from repro import obs
    _, red = synthetic()
    obs.clear()
    assert ps.records() is None            # no train.init recorded
    assert ps.idle_share({"trace": red}, ("train.data",)) is None
    with obs.span("train.init"):
        pass
    with obs.span("train.run"):
        pass
    with obs.span("train.init"):
        pass
    assert [r.name for r in ps.records()] == ["train.init"]
    assert ps.setup_seconds({}, "train.init") is None     # no trace
    # a program without the recorder (as before it had one)
    import repro
    monkeypatch.delattr(repro, "obs")
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert ps.records() is None
    assert ps.setup_seconds({"trace": red}, "train.init") is None


def test_join_on_a_recorded_trace(tmp_path):
    from jax.profiler import ProfileData
    from repro import obs
    f = jax.jit(lambda x: jnp.tanh(x @ x))
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    obs.clear()
    with obs.span("train.init"):
        pass
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation(bt.WINDOW_SPAN):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("bench.run"):
                    with obs.span("train.run"):
                        f(x).block_until_ready()
    red = bt.reduce_dir(str(tmp_path))
    j = ps.join(ps.records(), red.spans)
    assert 0 <= j.residual_ns < 1e6
    path = bt.find_xplane(str(tmp_path))
    (start,) = [dict(p.stats)["profile_start_time"]
                for p in ProfileData.from_file(path).planes
                if p.name == "Task Environment"]
    # the trace's clock counts from the profile's start
    assert abs(j.offset_ns + start) < 1e6
    chunks = [s for s in red.spans if s.name == "bench.run"]
    for (a, b), c in zip(j.shifted(("train.run",)), chunks):
        assert c.start <= a <= b <= c.end


def test_traced_run_reports_the_span_metrics(monkeypatch, tmp_path):
    from bench import peaks
    # the CPU has no published peak; a stand-in row lets the readers run
    monkeypatch.setitem(peaks.PEAKS, "cpu", dict(peaks.PEAKS["TPU v5 lite"]))
    res = helpers.drive(helpers.tiny_train_cell(), tmp=tmp_path, trace=True)
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert set(SPAN_METRICS) <= set(m), sorted(m)
    assert all(m[k] >= 0 for k in SPAN_METRICS[:4])
    assert m["train.setup_init_s"] > 0 and m["train.setup_compile_s"] > 0
    for k in SPAN_METRICS[4:]:
        assert 0 <= m[k] <= m["train.device_idle"] + 1e-9, (k, m)
    assert m["train.input_idle"] + m["train.control_idle"] <= \
        m["train.device_idle"] + 1e-9
