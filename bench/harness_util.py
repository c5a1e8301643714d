"""Small pieces every driver shares: seeds, the compile counter, the
kernel-presence check, and handing seeded weights to the program."""
from __future__ import annotations

import re

import jax
import numpy as np

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def derived_seed(seed: int) -> int:
    """A 31-bit seed drawn from any whole ``seed`` (the program's streams
    take a 32-bit key)."""
    return int(np.random.SeedSequence(int(seed)).generate_state(1)[0]
               & 0x7FFFFFFF)


def seed_key(seed: int) -> jax.Array:
    """A JAX key from any whole ``seed``, wider than 32 bits included."""
    s = int(seed)
    key = jax.random.PRNGKey(s & 0xFFFFFFFF)
    return jax.random.fold_in(key, (s >> 32) & 0xFFFFFFFF)


class CompileCounter:
    """Counts programs compiled by XLA and programs fetched from the
    persistent cache between ``start`` and ``stop``."""

    def __init__(self):
        self.on = False
        self.programs = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if self.on and event == COMPILE_EVENT:
            self.programs += 1

    def _event(self, event, **kw):
        if self.on and event == CACHE_HIT_EVENT:
            self.hits += 1

    def start(self):
        self.programs = self.hits = 0
        self.on = True

    def stop(self):
        """-> (programs compiled by XLA, programs read from the cache)."""
        self.on = False
        return self.programs - self.hits, self.hits


def kernels_in(exe) -> set:
    """Pallas kernels (by their jitted wrapper's name) compiled into an
    executable's TPU custom calls."""
    out = set()
    for line in exe.as_text().splitlines():
        if "tpu_custom_call" in line:
            m = re.search(r"jit\((\w+)\)/pallas_call", line)
            if m:
                out.add(m.group(1))
    return out


def seeded_task(task, params, aux):
    """``task`` whose ``init`` returns the benchmark's weights (wrapped with
    the program's own logical axes) in place of the program's random init.
    Call ``release()`` once the program holds its copy."""
    from repro.nn.module import merge_params, split_params
    like, _ = jax.eval_shape(task.init, jax.random.PRNGKey(0))
    _, axes = split_params(like)
    held = {"w": (merge_params(params, axes), aux)}

    class Seeded(type(task)):
        def init(self, key):
            del key
            return held["w"]

        def release(self):
            held.clear()

    return Seeded(task.cfg)
