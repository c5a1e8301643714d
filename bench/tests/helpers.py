"""Tiny stand-ins of the cells, for driving a whole run on the CPU."""
from __future__ import annotations

import json
import os
import time

from bench import run as bench_run

ROOT = bench_run.ROOT
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def spec():
    return bench_run.read_json(os.path.join(ROOT, "BENCHMARK.json"))


def small_limits(name):
    """Limits of a small stand-in (``data/limits-small.json``): the cells'
    own limits were read at the cells' sizes on the chip."""
    return bench_run.read_json(os.path.join(DATA, "limits-small.json"))[name]


def tiny_train_cell(**traffic):
    """The LM training cell with a two-layer configuration and short rows;
    the traffic's other parameters, the driver and the limits are the
    cell's own."""
    cell = bench_run.Cell(spec(), "smollm-135m.train-2k")
    with open(os.path.join(DATA, "tiny-lm.json")) as f:
        cell.config = json.load(f)
    tf = dict(cell.traffic, seq_len=64, rung_base=2, rung_max=4)
    tf.update(traffic)
    cell.traffic = tf
    cell.limits = small_limits("tiny-lm.train")
    return cell


def drive(cell, seed=7, seconds=0.5, trace=False, tmp="/tmp", **kw):
    """One run of ``cell`` on the local devices, the chip check skipped."""
    import jax
    # as on the chip: every program goes through the persistent cache
    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(str(tmp), "jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devs = jax.devices()[:cell.chips]
    return bench_run.execute(cell, seed, seconds, trace, devs, str(tmp),
                             t0=time.perf_counter(), **kw)
