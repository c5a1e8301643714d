"""The program's span recorder: nesting, outcomes, its bound, the
profiler's clock, and the spans a training run leaves behind."""
import glob
import os

import jax
import jax.numpy as jnp
import pytest

from repro import obs
from repro.core.precision import TriAccelConfig
from repro.models.lm import LMConfig
from repro.nn.attention import AttnConfig
from repro.nn.blocks import BlockDef, StackConfig
from repro.train.trainer import Trainer, TrainerConfig


def tiny_lm():
    attn = AttnConfig(d_model=32, num_heads=2, num_kv_heads=1, head_dim=16,
                      impl="naive")
    sc = StackConfig(segments=(((BlockDef("gqa", "dense"),), 1),),
                     d_model=32, d_ff=64, attn=attn, remat=False)
    return LMConfig(name="tiny", family="dense", vocab_size=64, stack=sc,
                    compute_dtype=jnp.float32)


def test_nesting_parents_and_attrs():
    obs.clear()
    with obs.span("outer", rung=4):
        with obs.span("inner", step=3) as inner:
            inner.attrs["late"] = "yes"
        with obs.span("second"):
            pass
    recs = {r.name: r for r in obs.spans()}
    assert [r.name for r in obs.spans()] == ["inner", "second", "outer"]
    assert recs["outer"].parent_id is None
    assert recs["inner"].parent_id == recs["outer"].id
    assert recs["second"].parent_id == recs["outer"].id
    assert recs["outer"].attrs == {"rung": 4}
    assert recs["inner"].attrs == {"step": 3, "late": "yes"}
    assert all(r.outcome == "ok" for r in recs.values())
    o, i = recs["outer"], recs["inner"]
    assert o.t0_ns <= i.t0_ns <= i.t1_ns <= o.t1_ns


def test_exception_is_recorded_and_raised():
    obs.clear()
    with pytest.raises(KeyError):
        with obs.span("outer"):
            with obs.span("inner"):
                raise KeyError("x")
    outcomes = {r.name: r.outcome for r in obs.spans()}
    assert outcomes == {"inner": "KeyError", "outer": "KeyError"}
    with obs.span("after"):        # the thread's stack unwound
        pass
    assert obs.spans()[-1].parent_id is None


def test_log_is_bounded():
    obs.clear()
    for i in range(obs.LOG_SIZE + 5):
        with obs.span("s", i=i):
            pass
    recs = obs.spans()
    assert len(recs) == obs.LOG_SIZE
    assert recs[0].attrs["i"] == 5
    assert recs[-1].attrs["i"] == obs.LOG_SIZE + 4
    obs.clear()
    assert obs.spans() == []


def test_span_is_a_host_event_on_the_profilers_clock(tmp_path):
    from jax.profiler import ProfileData
    f = jax.jit(lambda x: jnp.tanh(x @ x))
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    obs.clear()
    with jax.profiler.trace(str(tmp_path)):
        with obs.span("train.run", start=3, steps=2):
            with obs.step_span("train.step", 3, rung=4):
                f(x).block_until_ready()
    path = sorted(glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                            recursive=True))[-1]
    data = ProfileData.from_file(path)
    start = None
    events = {}
    for plane in data.planes:
        if plane.name == "Task Environment":
            start = dict(plane.stats)["profile_start_time"]
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("train."):
                    events[ev.name] = ev
    assert start is not None and set(events) == {"train.run", "train.step"}
    assert dict(events["train.step"].stats)["step_num"] == 3
    for rec in obs.spans():
        ev = events[rec.name]
        assert abs(start + ev.start_ns - rec.t0_ns) < 1e6, rec.name


def test_training_run_records_its_phases():
    tac = TriAccelConfig(ladder="tpu", t_ctrl=2, t_curv=3, b_curv=2,
                         curvature_method="fisher")
    tcfg = TrainerConfig(total_steps=8, seq_len=16, rungs=(2, 4),
                         log_every=1)
    obs.clear()
    tr = Trainer(tiny_lm(), tac, tcfg)
    tr.warm_rungs()
    tr.run(7)
    recs = obs.spans()
    by_id = {r.id: r for r in recs}

    def named(name):
        return [r for r in recs if r.name == name]

    def parent(r):
        return by_id[r.parent_id].name if r.parent_id else None

    assert [parent(r) for r in named("train.init")] == [None]
    assert sorted(r.attrs["rung"] for r in named("train.compile")) == [2, 4]
    assert tr.compile_count == len(named("train.compile"))
    assert all(r.outcome == "ok" for r in recs)
    (run,) = named("train.run")
    assert run.attrs == {"start": 0, "steps": 7}
    steps = named("train.step")
    assert [r.attrs["step_num"] for r in steps] == list(range(7))
    assert {r.parent_id for r in steps} == {run.id}
    assert {r.attrs["rung"] for r in steps} <= {2, 4}
    step_data = [r for r in named("train.data") if parent(r) == "train.step"]
    assert [r.attrs["step"] for r in step_data] == list(range(7))
    ticks = named("train.control")
    assert [r.attrs["step"] for r in ticks] == [2, 4, 6]
    assert all({"rung", "rung_after"} <= set(r.attrs) for r in ticks)
    curv = named("train.curvature")
    assert [r.attrs["step"] for r in curv] == [3, 6]
    assert {parent(r) for r in ticks + curv} == {"train.step"}


@pytest.mark.parametrize("error,outcome", [
    (RuntimeError("RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. "
                  "Ran out of memory in memory space hbm."), "oom"),
    (ValueError("bad shape"), "ValueError")])
def test_failed_compile_is_named_by_its_outcome(error, outcome):
    tac = TriAccelConfig(ladder="tpu", t_ctrl=2, enable_curvature=False)
    tr = Trainer(tiny_lm(), tac, TrainerConfig(seq_len=16, rungs=(2, 4)))

    def step_fn(state, batch):
        raise error
    tr._step_fn = step_fn
    obs.clear()
    with pytest.raises(type(error)):
        tr._get_step(4)
    (rec,) = [r for r in obs.spans() if r.name == "train.compile"]
    assert (rec.attrs, rec.outcome) == ({"rung": 4}, outcome)
    assert tr.compile_count == 0
