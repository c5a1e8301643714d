"""The registered hot paths the linter walks (DESIGN.md §12).

A ``HotPath`` is one traceable program whose invariants the rules check:

  * ``train/resident/<opt>`` — the slab-resident fused train step per
    optimizer (the Trainer's default step on all-floating params), built
    exactly the way ``Trainer.__init__`` builds it;
  * ``serve/<kind>/...`` — every executable a ``ServeEngine`` can
    dispatch (decode / chunked-prefill / whole-prompt admit / repack for
    token tasks, infer for cache-free ones) per (rung, tier), taken from
    ``ServeEngine.path_specs()`` so the linter sees the same functions
    ``warm()`` compiles;
  * ``kernel/<name>`` — representative traces of the standalone Pallas
    kernels (flash_attention fwd+bwd, qdq_cast, grad_stats) at
    production-like geometry; the fused_update pair is covered by the
    resident train paths.

Jaxprs and compiled executables are built lazily and cached per path, so
jaxpr-only rules never pay for XLA compilation. ``meta`` carries the flat
invar index ranges (weights, compute slab, donated args) the dataflow
rules seed from.
"""
from __future__ import annotations

import dataclasses
from typing import (Any, Callable, Dict, List, Optional, Sequence, Tuple)

import jax
import jax.numpy as jnp

SDS = jax.ShapeDtypeStruct

#: Default lint sweep (ISSUE/acceptance set): one LM, one vision task,
#: both in reduced geometry so the sweep is CI-fast.
DEFAULT_CONFIGS = ("smollm-135m", "resnet18")
DEFAULT_OPTIMIZERS = ("sgdm", "adamw")
DEFAULT_RUNGS = (1, 2)
DEFAULT_TIERS = (1, 2)

#: R6 allowance for train steps: the control update legitimately
#: all-reduces O(L) per-layer stats under sharding; anything bigger (or
#: any gather/scatter of weight-sized tensors) is a finding.
TRAIN_COLLECTIVE_ALLOWANCE = {"all-reduce": 2 << 20}


@dataclasses.dataclass
class HotPath:
    """One lintable program. ``jaxpr``/``hlo`` build lazily and cache."""
    name: str                      # e.g. "train/resident/sgdm"
    kind: str                      # core.KINDS member
    config: str                    # arch id
    jaxpr_fn: Callable[[], Any]
    compile_fn: Optional[Callable[[], Any]] = None
    meta: Dict[str, Any] = dataclasses.field(default_factory=dict)
    _jaxpr: Any = dataclasses.field(default=None, repr=False)
    _compiled: Any = dataclasses.field(default=None, repr=False)

    @property
    def jaxpr(self) -> Any:
        if self._jaxpr is None:
            self._jaxpr = self.jaxpr_fn()
        return self._jaxpr

    @property
    def compiled(self) -> Any:
        if self._compiled is None and self.compile_fn is not None:
            self._compiled = self.compile_fn()
        return self._compiled

    @property
    def hlo(self) -> Optional[str]:
        exe = self.compiled
        return None if exe is None else exe.as_text()


def _norm_config(config: str) -> str:
    return config.replace("_", "-")


def _leaf_count(tree: Any) -> int:
    return len(jax.tree.leaves(tree))


def _arg_range(args: Sequence[Any], pos: int) -> Tuple[int, int]:
    """Flat invar (start, count) covered by positional arg ``pos`` — the
    jaxpr invar order is the tree_flatten order of the argument tuple."""
    start = sum(_leaf_count(a) for a in args[:pos])
    return (start, _leaf_count(args[pos]))


def _leaf_index(args: Sequence[Any], leaf: Any) -> Tuple[int, int]:
    """(start, 1) flat range of one leaf, located by identity."""
    for i, v in enumerate(jax.tree.leaves(tuple(args))):
        if v is leaf:
            return (i, 1)
    raise ValueError("leaf not found in argument tree")


# ------------------------------------------------------------- train -----
def _train_setup(config: str):
    from repro.core.controller import init_control
    from repro.core.precision import TriAccelConfig
    from repro.kernels.layout import slab_view
    from repro.models.registry import get_task
    from repro.nn.module import split_params
    from repro.train.train_step import init_compute

    task = get_task(config, reduced=True)
    wrapped, aux = task.init(jax.random.PRNGKey(0))
    params, _ = split_params(wrapped)
    grouping = task.grouping(params)
    # the lint-stable control config: fixed codes, no curvature probes —
    # the step graph the Trainer runs between control refreshes
    tac = TriAccelConfig(ladder="tpu", t_ctrl=1000, enable_curvature=False)
    ctl = init_control(grouping.num_layers, tac)
    comp = init_compute(task, params, grouping, ctl, tac)
    view = slab_view(params, grouping)
    batch = task.data_stream(4, seq_len=16).batch(0)
    return task, params, aux, grouping, tac, ctl, comp, view, batch


def _make_opt(optname: str):
    from repro.optim.optimizers import adamw, sgdm
    if optname == "sgdm":
        return sgdm(0.9, weight_decay=1e-4)
    if optname == "adamw":
        return adamw(weight_decay=1e-2)
    raise KeyError(f"unknown optimizer {optname!r}")


def train_paths(config: str,
                optimizers: Sequence[str] = DEFAULT_OPTIMIZERS
                ) -> List[HotPath]:
    """The slab-resident fused step per optimizer — the production train
    path for every all-floating task (Trainer auto-residency)."""
    from repro.train.train_step import (TrainState, make_train_step,
                                        pack_state)
    config = _norm_config(config)
    (task, params, aux, grouping, tac, ctl, comp, view,
     batch) = _train_setup(config)
    if not all(jnp.issubdtype(l.dtype, jnp.floating)
               for l in jax.tree.leaves(params)):
        return []  # no resident path for mixed-dtype params trees
    out = []
    for optname in optimizers:
        opt = _make_opt(optname)
        step = make_train_step(task, tac, opt, grouping,
                               lambda s: jnp.asarray(1e-3),
                               fused_update=True, resident_params=params)
        state = pack_state(view, TrainState(params, aux, opt.init(params),
                                            ctl, comp),
                           task.compute_dtype)
        args = (state, batch)
        meta = {
            "rows": int(view.rows),
            "compute_slab": [_leaf_index(args, state.compute["slab"])],
            "weights": [_leaf_index(args, state.compute["slab"]),
                        _leaf_index(args, state.params)],
            "donated": [_arg_range(args, 0)],
            "collective_allowance": dict(TRAIN_COLLECTIVE_ALLOWANCE),
        }

        def jx(step=step, args=args):
            return jax.make_jaxpr(step)(*args)

        def exe(step=step, args=args):
            sds = jax.tree.map(lambda x: SDS(x.shape, x.dtype), args)
            return jax.jit(step, donate_argnums=(0,)).lower(*sds).compile()

        out.append(HotPath(name=f"train/resident/{optname}", kind="train",
                           config=config, jaxpr_fn=jx, compile_fn=exe,
                           meta=meta))
    return out


# ------------------------------------------------------------- serve -----
def serve_paths(config: str, rungs: Sequence[int] = DEFAULT_RUNGS,
                tiers: Sequence[int] = DEFAULT_TIERS,
                prefill_chunk: int = 4) -> List[HotPath]:
    """Every executable a ServeEngine dispatches at (rungs x tiers), via
    ``ServeEngine.path_specs()`` — identical functions + abstract args to
    the ones ``warm()`` compiles, donation included."""
    from repro.models.registry import get_task
    from repro.nn.module import split_params
    from repro.serve import ServeEngine

    config = _norm_config(config)
    task = get_task(config, reduced=True)
    wrapped, aux = task.init(jax.random.PRNGKey(0))
    params, _ = split_params(wrapped)
    engine = ServeEngine(task, params, aux, total_len=64, prompt_len=8,
                         rungs=tuple(sorted(set(rungs))), tiers=tiers,
                         prefill_chunk=prefill_chunk)
    out = []
    for key, fn, args, donate in engine.path_specs():
        kind = key[0]
        if kind == "repack":
            name = f"serve/repack/r{key[1]}->r{key[2]}"
        else:
            name = f"serve/{kind}/r{key[1]}/t{key[2]}"
        meta: Dict[str, Any] = {
            "weights": [] if kind == "repack" else [_arg_range(args, 0)],
            "donated": [_arg_range(args, pos) for pos in donate],
            "collective_allowance": {},
        }

        def jx(fn=fn, args=args):
            return jax.make_jaxpr(fn)(*args)

        def exe(engine=engine, key=key):
            return engine.compiled(key)

        out.append(HotPath(name=name, kind=kind, config=config,
                           jaxpr_fn=jx, compile_fn=exe, meta=meta))
    return out


# ------------------------------------------------------------ kernels ----
def kernel_paths() -> List[HotPath]:
    """Standalone Pallas kernel traces at production-like geometry. The
    fused_update stats/apply pair is linted where it ships — inside the
    resident train paths — so only the kernels those paths don't reach
    (flash attention fwd+bwd, the QDQ cast, grad_stats) are traced here."""
    from repro.kernels import ops

    def flash_jx():
        q = SDS((2, 512, 4, 64), jnp.float32)
        kv = SDS((2, 512, 2, 64), jnp.float32)

        def fwd_bwd(q_, k_, v_):
            def loss(q_, k_, v_):
                return jnp.sum(ops.flash_attention(q_, k_, v_, causal=True))
            return jax.value_and_grad(loss, argnums=(0, 1, 2))(q_, k_, v_)

        return jax.make_jaxpr(fwd_bwd)(q, kv, kv)

    def flash_packed_jx():
        # packed multi-document batch: segment masking active in forward
        # AND both backward kernels
        q = SDS((2, 512, 4, 64), jnp.float32)
        kv = SDS((2, 512, 2, 64), jnp.float32)
        seg = SDS((2, 512), jnp.int32)

        def fwd_bwd(q_, k_, v_, seg_):
            def loss(q_, k_, v_):
                return jnp.sum(ops.flash_attention(
                    q_, k_, v_, segments=seg_, causal=True))
            return jax.value_and_grad(loss, argnums=(0, 1, 2))(q_, k_, v_)

        return jax.make_jaxpr(fwd_bwd)(q, kv, kv, seg)

    def flash_mla_jx():
        # MLA geometry: qk head dim (nope+rope=192) != v head dim (128),
        # tiled with the independent Dv BlockSpec
        q = SDS((2, 512, 4, 192), jnp.float32)
        kk = SDS((2, 512, 4, 192), jnp.float32)
        v = SDS((2, 512, 4, 128), jnp.float32)

        def fwd_bwd(q_, k_, v_):
            def loss(q_, k_, v_):
                return jnp.sum(ops.flash_attention(
                    q_, k_, v_, causal=True, scale=192 ** -0.5))
            return jax.value_and_grad(loss, argnums=(0, 1, 2))(q_, k_, v_)

        return jax.make_jaxpr(fwd_bwd)(q, kk, v)

    def flash_decode_ragged_jx():
        # per-slot-length decode: (B,) length vector is a runtime operand
        q = SDS((4, 1, 4, 64), jnp.float32)
        kv = SDS((4, 256, 2, 64), jnp.float32)
        lengths = SDS((4,), jnp.int32)
        return jax.make_jaxpr(
            lambda q_, k_, v_, l_: ops.flash_decode(q_, k_, v_, l_))(
                q, kv, kv, lengths)

    def qdq_jx():
        x = SDS((1024, 512), jnp.float32)
        return jax.make_jaxpr(
            lambda x_: ops.qdq_cast(x_, jnp.asarray(0, jnp.int32)))(x)

    def stats_jx():
        x = SDS((1_000_000,), jnp.float32)
        return jax.make_jaxpr(ops.grad_stats)(x)

    mk = [("kernel/flash_attention", flash_jx),
          ("kernel/flash_attention_packed", flash_packed_jx),
          ("kernel/flash_attention_mla", flash_mla_jx),
          ("kernel/flash_decode_ragged", flash_decode_ragged_jx),
          ("kernel/qdq_cast", qdq_jx),
          ("kernel/grad_stats", stats_jx)]
    return [HotPath(name=n, kind="kernel", config="<kernels>", jaxpr_fn=f,
                    meta={"weights": [], "donated": [],
                          "collective_allowance": {}})
            for n, f in mk]


def config_paths(config: str, *, serve: bool = True,
                 optimizers: Sequence[str] = DEFAULT_OPTIMIZERS,
                 rungs: Sequence[int] = DEFAULT_RUNGS,
                 tiers: Sequence[int] = DEFAULT_TIERS) -> List[HotPath]:
    """All registered hot paths of one config: train + serve."""
    paths = train_paths(config, optimizers=optimizers)
    if serve:
        paths += serve_paths(config, rungs=rungs, tiers=tiers)
    return paths
