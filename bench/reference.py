"""Plain reference of the training update the training cells run, and the
numbers that compare the program's first steps with it.

The update is SGD with momentum over float32 masters: the global gradient
norm is clipped to ``grad_clip``, the momentum is ``m = beta*m + g``, and
step ``t`` (counted from 1) moves the weights by ``-lr(t) * m`` under a
linear warm-up and cosine decay. The curvature scale is 1 and no precision
tier applies: the first steps run before any curvature or code refresh.
"""
from __future__ import annotations

import math
from typing import Callable, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np


def lr_at(t: int, o: dict) -> float:
    base, warm, total = o["base_lr"], o["warmup_steps"], o["total_steps"]
    final = o.get("final_frac", 0.05)
    if t < warm:
        return base * t / max(warm, 1)
    x = min(max((t - warm) / max(total - warm, 1), 0.0), 1.0)
    return base * (final + (1 - final) * 0.5 * (1 + math.cos(math.pi * x)))


def leaf_norms(tree) -> Dict[str, float]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    norms = [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
             for _, x in flat]
    vals = jax.device_get(norms)
    return {jax.tree_util.keystr(p): float(v)
            for (p, _), v in zip(flat, vals)}


def diff_norms(a, b) -> Dict[str, float]:
    return leaf_norms(jax.tree.map(
        lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b))


def sgdm_steps(value_and_grad: Callable, params, aux, batches: Sequence,
               opt: dict) -> dict:
    """Run the reference update over ``batches`` from ``params``."""
    p0 = params
    mu = jax.tree.map(jnp.zeros_like, params)
    losses: List[float] = []
    out: dict = {}
    for t, batch in enumerate(batches):
        loss, g, aux = value_and_grad(params, aux, batch)
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(g)))
        clip = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(gn, 1e-9))
        if t == 0:
            out["raw_grad_norms"] = leaf_norms(g)
        mu = jax.tree.map(lambda m, x: opt["momentum"] * m + x * clip, mu, g)
        if t == 0:
            out["grad_norms"] = leaf_norms(mu)
        lr = lr_at(t + 1, opt)
        params = jax.tree.map(lambda p, m: p - lr * m, params, mu)
        losses.append(float(loss))
    out["losses"] = losses
    out["delta_norms"] = diff_norms(params, p0)
    return out


def leaf_gaps(prog: Dict[str, float], ref: Dict[str, float],
              leaves: Sequence[str]) -> Dict[str, float]:
    """Each leaf's gap between the program's and the reference's norm,
    over the larger of that leaf's reference norm and the median leaf's."""
    med = float(np.median([ref[k] for k in leaves]))
    return {k: abs(prog[k] - ref[k]) / max(ref[k], med) for k in leaves}


def moving_leaves(raw_grad_norms: Dict[str, float],
                  rel: float = 1e-3) -> List[str]:
    """Leaves whose reference gradient is above ``rel`` of the median
    leaf's: the rest move by round-off alone."""
    med = float(np.median(list(raw_grad_norms.values())))
    return sorted(k for k, v in raw_grad_norms.items() if v > rel * med)


def numbers(prog: dict, ref: dict) -> Dict[str, tuple]:
    """Every number a training cell can be held to, as (value, where):
    the largest relative loss gap over the steps; the worst leaf's and the
    median leaf's gap of the first gradient; the same of the weights'
    change, over the leaves that move."""
    loss = max(abs(a - b) / abs(b)
               for a, b in zip(prog["losses"], ref["losses"]))
    if not all(math.isfinite(a) for a in prog["losses"]):
        loss = math.inf
    out = {"loss_gap": (loss, "")}
    for name, key, leaves in (
            ("grad_gap", "grad_norms", sorted(ref["grad_norms"])),
            ("update_gap", "delta_norms",
             moving_leaves(ref["raw_grad_norms"]))):
        g = {k: v if math.isfinite(v) else math.inf
             for k, v in leaf_gaps(prog[key], ref[key], leaves).items()}
        worst = max(g, key=lambda k: g[k])
        out[name] = (g[worst], worst)
        out[name + "_median"] = (float(np.median(list(g.values()))),
                                 f"median of {len(g)} leaves")
    return out


def compare(prog: dict, ref: dict, limits: dict, **counted) -> List[dict]:
    """The numbers a cell's limits name, each with its limit; ``counted``
    adds numbers the driver counted itself (a count of misses, limit 0)."""
    nums = numbers(prog, ref)
    nums.update({k: (float(v), "") for k, v in counted.items()})
    out = []
    for name, lim in limits.items():
        v, where = nums[name]
        out.append({"name": name, "value": v, "limit": lim,
                    "ok": bool(v <= lim), "where": where})
    return out
