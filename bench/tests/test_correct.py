"""A whole run on the CPU at a small size, the chip check skipped: a sound
run is correct, and ``correct`` comes out false when the timed path is
broken underneath (the state left unchanged; half of the batch left out,
the mean taken over the rest; precision codes chosen below the threshold
rule in the window) and for the configuration's control (the program's own
lower tier)."""
import pytest

from bench import faults
from bench.tests import helpers

CELLS = {"lm": helpers.tiny_train_cell}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_sound_run_is_correct(cell, tmp_path):
    c = CELLS[cell]()
    res = helpers.drive(c, tmp=tmp_path)
    assert res["correct"] is True, res["checks"]
    assert [x["name"] for x in res["checks"]] == list(c.limits)


@pytest.mark.parametrize("cell", sorted(CELLS))
@pytest.mark.parametrize("fault", sorted(faults.TRAIN))
def test_broken_step_is_not_correct(cell, fault, monkeypatch, tmp_path):
    faults.plant_train(fault, monkeypatch.setattr)
    res = helpers.drive(CELLS[cell](), tmp=tmp_path)
    assert res["correct"] is False, res["checks"]


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_control_is_not_correct(cell, tmp_path):
    res = helpers.drive(CELLS[cell](), tmp=tmp_path, control=True)
    assert res["correct"] is False, res["checks"]


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_codes_below_the_rule_are_not_correct(cell, monkeypatch, tmp_path):
    import jax.numpy as jnp
    import repro.core.controller as controller
    real = controller.codes_from_stats
    monkeypatch.setattr(controller, "codes_from_stats",
                        lambda *a: jnp.maximum(real(*a) - 1, 0))
    c = CELLS[cell]()
    # every layer's rule code is above the low tier, so a lowered one shows
    c.traffic = dict(c.traffic, triaccel=dict(c.traffic["triaccel"],
                                               tau_low=0.0))
    res = helpers.drive(c, tmp=tmp_path)
    misses = {x["name"]: x["value"] for x in res["checks"]}
    assert misses["code_rule_misses"] > 0, res["checks"]
    assert res["correct"] is False


def test_traced_run_reports_per_layer_metrics(monkeypatch, tmp_path):
    from bench import peaks
    # the CPU has no published peak; a stand-in row lets the readers run
    monkeypatch.setitem(peaks.PEAKS, "cpu", dict(peaks.PEAKS["TPU v5 lite"]))
    res = helpers.drive(helpers.tiny_train_cell(), tmp=tmp_path, trace=True)
    m = res["metrics"]
    assert {"train.device_idle", "train.step_mfu"} <= set(m)
    assert all(0 <= v["value"] <= 100 for v in m.values()), m
    assert 0 < res["device"]["busy_s"] <= res["device"]["window_s"]
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
