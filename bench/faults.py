"""Faults planted under the timed path, to show that ``correct`` catches
them: the calibration run on the chip reads each one's numbers, and the
CPU tests see ``correct`` come out false."""
from __future__ import annotations

import jax


def unchanged(step):
    """A step that returns its state unchanged."""
    def f(state, batch):
        _, metrics = step(state, batch)
        return state, metrics
    return f


def half_batch(step):
    """A step that leaves out half of the batch and takes the mean over the
    rest."""
    def f(state, batch):
        return step(state, jax.tree.map(lambda x: x[: x.shape[0] // 2],
                                        batch))
    return f


TRAIN = {"state_unchanged": unchanged, "half_batch": half_batch}


def plant_train(name: str, setattr_=setattr):
    """Break the program's training step with fault ``name``; returns a
    function that restores it. ``setattr_`` may be pytest's
    ``monkeypatch.setattr``."""
    import repro.train.trainer as trainer_mod
    real = trainer_mod.make_train_step
    wrap = TRAIN[name]
    setattr_(trainer_mod, "make_train_step",
             lambda *a, **k: wrap(real(*a, **k)))
    return lambda: setattr(trainer_mod, "make_train_step", real)
