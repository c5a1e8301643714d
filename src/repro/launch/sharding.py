"""Logical-axis -> mesh PartitionSpec rules (TP on "model", FSDP on
"data"(+"pod"), EP for experts), with divisibility-aware fallbacks.

Every Param carries logical axis names; these rules turn an (axes, shape)
pair into a PartitionSpec. A dimension that is not divisible by its mesh
axes falls back to replication; a mesh axis is used at most once per tensor
(first logical dim wins — e.g. MoE w_gate ("expert","embed","mlp") gives
experts the model axis and leaves "mlp" replicated = expert parallelism).
1-D parameters (norm scales, biases) are replicated.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# logical axis -> preferred mesh axes, in priority order. "fsdp" expands to
# ("pod","data") on a multi-pod mesh, ("data",) otherwise.
RULES: Dict[Optional[str], Tuple[str, ...]] = {
    "vocab": ("model",),
    "heads": ("model",),
    "kv": ("model",),
    "mlp": ("model",),
    "mlp2": ("fsdp",),
    "expert": ("model",),
    "embed": ("fsdp",),
    "eembed": ("fsdp",),
    "emlp": (),
    "kvlora": (),
    "qlora": (),
    "layers": (),
    None: (),
}


def fsdp_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def host_max(value: float) -> float:
    """Max of a host-local scalar over all hosts.

    Used to reduce per-host measured executable footprints
    (``memory_analysis()`` is addressable-device-local) so the §3.3 rung
    decision is safe on the MOST-loaded host of an uneven mesh. Single
    process — every test/CPU run — is the identity, no device traffic."""
    if jax.process_count() == 1:
        return float(value)
    from jax.experimental import multihost_utils
    gathered = multihost_utils.process_allgather(
        jnp.asarray(value, jnp.float32))
    return float(np.max(np.asarray(gathered)))


def harvested_exe_bytes(compiled) -> Optional[float]:
    """``measured_exe_bytes`` + the host_max reduction, shared by
    Trainer and ServeEngine so the harvest invariant lives once: EVERY
    host enters the collective even when its local harvest came up empty
    (a conditional all-gather deadlocks the mesh — hence the -1 sentinel),
    and only a positive reduced footprint counts as a measurement."""
    from repro.core.batch_scaler import measured_exe_bytes
    mb = measured_exe_bytes(compiled)
    mb = host_max(mb if mb is not None else -1.0)
    return mb if mb > 0 else None


# -------------------------------------------------- activation constraints -
# XLA SPMD can replicate loop carries (the residual stream inside the layer
# scan), turning every projection into a full-batch all-reduce. Production
# frameworks pin activation shardings explicitly; ``constrain`` is a no-op
# unless a mesh has been installed via ``activation_mesh``.
import contextlib
import threading

_ACT = threading.local()


@contextlib.contextmanager
def activation_mesh(mesh: Optional[Mesh]):
    prev = getattr(_ACT, "mesh", None)
    _ACT.mesh = mesh
    try:
        yield
    finally:
        _ACT.mesh = prev


def current_mesh() -> Optional[Mesh]:
    """The mesh installed by ``activation_mesh`` (None outside one)."""
    return getattr(_ACT, "mesh", None)


def constrain(x, dims: Tuple[Optional[str], ...]):
    """dims entries: "batch" (fsdp axes), "model", or None. Skips any dim the
    mesh doesn't divide; no-op outside an activation_mesh context."""
    mesh = current_mesh()
    if mesh is None or x.ndim != len(dims):
        return x
    spec = []
    used = set()
    for name, size in zip(dims, x.shape):
        if name == "batch":
            axes = tuple(a for a in fsdp_axes(mesh) if a not in used)
        elif name == "model" and "model" in mesh.axis_names:
            axes = ("model",) if "model" not in used else ()
        else:
            axes = ()
        if axes and size % _axis_size(mesh, axes) == 0 and size > 1:
            spec.append(axes if len(axes) > 1 else axes[0])
            used.update(axes)
        elif axes and len(axes) > 1 and size % mesh.shape[axes[-1]] == 0 and size > 1:
            spec.append(axes[-1])
            used.add(axes[-1])
        else:
            spec.append(None)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*spec)))


def constrain_tree_batch(tree):
    """Constrain dim0 (dim1 for mrope_positions) of every leaf to the dp axes."""
    def one(path, x):
        key = path[-1].key if path and hasattr(path[-1], "key") else ""
        if key == "mrope_positions":
            return constrain(x, (None, "batch") + (None,) * (x.ndim - 2))
        return constrain(x, ("batch",) + (None,) * (x.ndim - 1))
    return jax.tree_util.tree_map_with_path(one, tree)


def _axis_size(mesh: Mesh, axes) -> int:
    return int(np.prod([mesh.shape[a] for a in axes])) if axes else 1


def spec_for(axes: Sequence[Optional[str]], shape: Sequence[int],
             mesh: Mesh, overrides: Optional[Dict] = None) -> P:
    if len(shape) < 2:
        return P()
    rules = dict(RULES, **overrides) if overrides else RULES
    used = set()
    out = []
    for name, dim in zip(axes, shape):
        choice = None
        for pref in rules.get(name, ()):  # resolve "fsdp" to concrete axes
            mesh_axes = fsdp_axes(mesh) if pref == "fsdp" else (pref,)
            mesh_axes = tuple(a for a in mesh_axes if a in mesh.axis_names
                              and a not in used)
            if not mesh_axes:
                continue
            if dim % _axis_size(mesh, mesh_axes) == 0:
                choice = mesh_axes if len(mesh_axes) > 1 else mesh_axes[0]
                used.update(mesh_axes)
                break
            # try a prefix (e.g. only "data" when (pod,data) doesn't divide)
            if len(mesh_axes) > 1 and dim % mesh.shape[mesh_axes[-1]] == 0:
                choice = mesh_axes[-1]
                used.add(mesh_axes[-1])
                break
        out.append(choice)
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def param_shardings(axes_tree, shape_tree, mesh: Mesh, overrides=None):
    """NamedSharding tree for params given the axes tree from split_params.

    ``overrides`` remaps logical axes, e.g. {"embed": ()} produces the
    ZeRO-1 compute layout: TP intact, FSDP dim replicated (master/optimizer
    stay fully sharded; only the bf16 compute copy is gathered)."""
    leaves_s, treedef = jax.tree.flatten(shape_tree)
    leaves_a = treedef.flatten_up_to(axes_tree)
    out = [NamedSharding(mesh, spec_for(a, s.shape, mesh, overrides))
           for s, a in zip(leaves_s, leaves_a)]
    return treedef.unflatten(out)


def replicated(mesh: Mesh):
    return NamedSharding(mesh, P())


def slab_sharding(mesh: Mesh, shards: int = 1) -> NamedSharding:
    """Row-range sharding for the resident (rows, 512) optimizer-state
    slabs: the leading row axis is laid out over the fsdp axes — an
    EXPLICIT contract aligned to the 256-row block grid (SlabView pads
    rows to a multiple of SLAB_M * shards), never a compiler-chosen pack
    layout. Replicated when unsharded (dev mesh, single device)."""
    dp = fsdp_axes(mesh)
    if shards <= 1 or not dp:
        return NamedSharding(mesh, P())
    return NamedSharding(mesh, P(dp if len(dp) > 1 else dp[0], None))


def batch_shardings(batch_sds: Dict[str, Any], mesh: Mesh):
    """Shard the global-batch dim over (pod, data); mrope_positions carries
    batch on axis 1."""
    dp = fsdp_axes(mesh)
    dp_size = _axis_size(mesh, dp)
    out = {}
    for k, v in batch_sds.items():
        bdim = 1 if k == "mrope_positions" else 0
        if v.shape[bdim] % dp_size == 0 and v.shape[bdim] > 0:
            spec = [None] * len(v.shape)
            spec[bdim] = dp if len(dp) > 1 else dp[0]
            out[k] = NamedSharding(mesh, P(*spec))
        elif len(dp) > 1 and v.shape[bdim] % mesh.shape[dp[-1]] == 0:
            spec = [None] * len(v.shape)
            spec[bdim] = dp[-1]
            out[k] = NamedSharding(mesh, P(*spec))
        else:
            out[k] = replicated(mesh)
    return out


def cache_shardings(cache_sds, mesh: Mesh):
    """Decode/prefill cache shardings.

    Heuristic per leaf (after skipping the stacked-layer leading dim that
    every `segN` subtree carries): shard the batch dim over (pod,data) when
    divisible; otherwise (long-context batch=1) shard the *sequence* dim —
    distributed KV with XLA inserting the softmax collectives. A heads-like
    dim additionally shards over "model" when divisible.
    """
    dp = fsdp_axes(mesh)
    dp_size = _axis_size(mesh, dp)
    model = "model" if "model" in mesh.axis_names else None
    msize = mesh.shape[model] if model else 1

    def one(path, sds):
        shape = sds.shape
        # stacked segments: dim 0 is the scan-over-layers repeat
        stacked = any(getattr(p, "key", "").startswith("seg") for p in path)
        o = 1 if (stacked and len(shape) >= 2) else 0
        spec: list = [None] * len(shape)
        if len(shape) <= o:
            return replicated(mesh)
        used_dp = False
        # batch dim
        if shape[o] % dp_size == 0 and shape[o] > 1:
            spec[o] = dp if len(dp) > 1 else dp[0]
            used_dp = True
        # sequence dim for (B, S, ...) caches when batch couldn't shard
        if not used_dp and len(shape) >= o + 2 and shape[o + 1] % dp_size == 0 \
                and shape[o + 1] >= dp_size:
            spec[o + 1] = dp if len(dp) > 1 else dp[0]
            used_dp = True
        # a heads-like dim over model
        if model:
            for d in range(o + 1, len(shape)):
                if spec[d] is None and shape[d] % msize == 0 and shape[d] >= msize:
                    if d == len(shape) - 1 and shape[d] <= 256:
                        continue  # don't shard tiny trailing head_dims
                    spec[d] = model
                    break
        while spec and spec[-1] is None:
            spec.pop()
        return NamedSharding(mesh, P(*spec))

    return jax.tree_util.tree_map_with_path(one, cache_sds)


def state_shardings_like(param_sh, opt_state_shape):
    """Optimizer-state shardings mirroring the param tree (momentum etc.).

    Works structurally: any subtree of opt_state that matches the params
    treedef gets the param shardings; scalars are replicated.
    """
    def mirror(sub):
        try:
            return jax.tree.map(lambda _, s: s, sub, param_sh)
        except (ValueError, TypeError):
            return None

    out = {}
    for k, v in opt_state_shape.items():
        m = mirror(v)
        if m is not None:
            out[k] = m
        else:
            mesh = jax.tree.leaves(param_sh)[0].mesh
            out[k] = jax.tree.map(lambda _: NamedSharding(mesh, P()), v)
    return out
