"""smollm-135m: how the benchmark builds it, its seeded weights, and its
plain float32 reference.

The reference follows the published Llama block (RMSNorm, rotary
embeddings on q and k with the split-half convention, grouped-query
attention, SwiGLU MLP, tied output head) in straightforward ``jax.numpy``
at ``highest`` matmul precision. It imports nothing of the program: it
reads the weights by the names of the tree ``init_weights`` builds. Its
one departure, shared with the program: RMSNorm gains stored as an offset
from 1.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import counts


def _sizes(c):
    return counts.lm_shape(c)


# ------------------------------------------------------------- program ----
def make_task(c):
    """The program's training task at the file's sizes; for the published
    name, checked against the program's own registered configuration in
    everything but the RMSNorm epsilon, which the file gives as published
    (the registered configuration keeps 1e-6)."""
    import dataclasses
    from repro.models.lm import LMConfig
    from repro.nn.attention import AttnConfig
    from repro.nn.blocks import BlockDef, StackConfig
    from repro.train.task import LMTask
    s = _sizes(c)
    attn = AttnConfig(d_model=s["d"], num_heads=s["heads"],
                      num_kv_heads=s["kv"], head_dim=s["head_dim"],
                      rope_theta=float(c["rope_theta"]), impl=c["attn_impl"])
    stack = StackConfig(segments=(((BlockDef("gqa", "dense"),),
                                   s["layers"]),),
                        d_model=s["d"], d_ff=s["ff"], attn=attn,
                        act=c["hidden_act"], norm_eps=float(c["rms_norm_eps"]))
    cfg = LMConfig(name=c["name"], family="dense", vocab_size=s["vocab"],
                   stack=stack, tie_embeddings=bool(c["tie_word_embeddings"]))
    if c["name"] == "smollm-135m":
        from repro.configs import smollm_135m
        reg = smollm_135m.config()
        reg = dataclasses.replace(reg, stack=dataclasses.replace(
            reg.stack, norm_eps=cfg.stack.norm_eps))
        if cfg != reg:
            raise ValueError("bench/configs/smollm-135m.json no longer "
                             "matches repro.configs.smollm_135m.config()")
    return LMTask(cfg)


def train_flops_per_sample(c, traffic) -> float:
    return counts.lm_train_flops_per_sample(c, int(traffic["seq_len"]))


# ------------------------------------------------------------- weights ----
def init_weights(c, key, dtype=jnp.float32):
    """Random weights from ``key`` in the program's tree layout, built on
    the device in one jitted call. Returns (params, aux_state)."""
    s = _sizes(c)
    L, d, H, K, D, F, V = (s["layers"], s["d"], s["heads"], s["kv"],
                           s["head_dim"], s["ff"], s["vocab"])

    def tn(k, shape, fan_in):
        w = jax.random.truncated_normal(k, -2.0, 2.0, shape, jnp.float32)
        return (w / jnp.sqrt(jnp.float32(fan_in))).astype(dtype)

    @jax.jit
    def build(key):
        ks = jax.random.split(key, 8)
        blk = {
            "mix": {"wq": {"kernel": tn(ks[0], (L, d, H, D), d)},
                    "wk": {"kernel": tn(ks[1], (L, d, K, D), d)},
                    "wv": {"kernel": tn(ks[2], (L, d, K, D), d)},
                    "wo": {"kernel": tn(ks[3], (L, H, D, d), H * D)}},
            "ffn": {"w_gate": {"kernel": tn(ks[4], (L, d, F), d)},
                    "w_up": {"kernel": tn(ks[5], (L, d, F), d)},
                    "w_down": {"kernel": tn(ks[6], (L, F, d), F)}},
            "norm1": {"scale": jnp.zeros((L, d), dtype)},
            "norm2": {"scale": jnp.zeros((L, d), dtype)},
        }
        emb = 0.02 * jax.random.normal(ks[7], (V, d), jnp.float32)
        return {"embed": {"table": emb.astype(dtype)},
                "final_norm": {"scale": jnp.zeros((d,), dtype)},
                "stack": {"seg0": {"b0": blk}}}

    return build(key), {}


# ----------------------------------------------------------- reference ----
def _rms(x, offset, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + offset)


def _rope(x, pos, theta):
    """x: (S, heads, D); split-half rotation."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(c, x, w, pos):
    s = _sizes(c)
    eps, theta = float(c["rms_norm_eps"]), float(c["rope_theta"])
    H, K, D = s["heads"], s["kv"], s["head_dim"]
    h = _rms(x, w["norm1"], eps)
    q = _rope(jnp.einsum("sd,dhk->shk", h, w["wq"]), pos, theta)
    k = _rope(jnp.einsum("sd,dhk->shk", h, w["wk"]), pos, theta)
    v = jnp.einsum("sd,dhk->shk", h, w["wv"])
    rep = H // K
    k = jnp.repeat(k, rep, axis=1)            # query head h reads kv h//rep
    v = jnp.repeat(v, rep, axis=1)
    sc = jnp.einsum("qhd,khd->hqk", q, k) * (D ** -0.5)
    S = x.shape[0]
    mask = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    sc = jnp.where(mask[None], sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v)
    x = x + jnp.einsum("shk,hkd->sd", o, w["wo"])
    h2 = _rms(x, w["norm2"], eps)
    g = jax.nn.silu(h2 @ w["w_gate"]) * (h2 @ w["w_up"])
    return x + g @ w["w_down"]


def _stacked(params):
    b = params["stack"]["seg0"]["b0"]
    return {"norm1": b["norm1"]["scale"], "norm2": b["norm2"]["scale"],
            "wq": b["mix"]["wq"]["kernel"], "wk": b["mix"]["wk"]["kernel"],
            "wv": b["mix"]["wv"]["kernel"], "wo": b["mix"]["wo"]["kernel"],
            "w_gate": b["ffn"]["w_gate"]["kernel"],
            "w_up": b["ffn"]["w_up"]["kernel"],
            "w_down": b["ffn"]["w_down"]["kernel"]}


def ref_hidden(c, params, tokens):
    """Final hidden states (S, d) of one row, in float32."""
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    pos = jnp.arange(tokens.shape[0])
    x = params["embed"]["table"][tokens]

    @jax.checkpoint
    def body(x, w):
        return _layer(c, x, w, pos), None

    x, _ = jax.lax.scan(body, x, _stacked(params))
    return _rms(x, params["final_norm"]["scale"], float(c["rms_norm_eps"]))


def ref_logits(c, params, tokens):
    """Logits (S, vocab) of one row, in float32."""
    h = ref_hidden(c, params, tokens)
    return h @ params["embed"]["table"].astype(jnp.float32).T


def _row_nll(c, params, tokens, labels, chunk=512):
    h = ref_hidden(c, params, tokens)
    table = params["embed"]["table"].astype(jnp.float32)
    S = h.shape[0]
    chunk = min(chunk, S)

    @jax.checkpoint
    def body(acc, xs):
        hc, yc = xs
        lg = hc @ table.T
        nll = jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(
            lg, yc[:, None], -1)[:, 0]
        return acc + jnp.sum(nll), None

    tot, _ = jax.lax.scan(body, jnp.float32(0.0),
                          (h.reshape(S // chunk, chunk, -1),
                           labels.reshape(S // chunk, chunk)))
    return tot


def ref_value_and_grad_fn(c, batch_rows: int, rows_per_block: int = 2):
    """The jitted (params, tokens, labels) -> (loss, grads) of
    ``ref_value_and_grad`` for ``batch_rows`` rows."""
    rb = min(rows_per_block, batch_rows)
    while batch_rows % rb:
        rb -= 1

    @jax.jit
    def run(params, tokens, labels):
        B, S = tokens.shape
        blocks = (tokens.reshape(B // rb, rb, S),
                  labels.reshape(B // rb, rb, S))

        def block_sum(p, t, y):
            return jnp.sum(jax.vmap(lambda a, b: _row_nll(c, p, a, b))(t, y))

        def body(acc, xs):
            nll, g = acc
            v, gb = jax.value_and_grad(block_sum)(params, *xs)
            return (nll + v, jax.tree.map(jnp.add, g, gb)), None

        zero = jax.tree.map(jnp.zeros_like, params)
        (nll, g), _ = jax.lax.scan(body, (jnp.float32(0.0), zero), blocks)
        n = jnp.float32(B * S)
        return nll / n, jax.tree.map(lambda a: a / n, g)
    return run


def ref_value_and_grad(c, params, aux, batch):
    """Mean next-token loss over ``batch`` and its gradient, in float32 at
    highest matmul precision, accumulated over blocks of rows so that it
    fits on the chip. Returns (loss, grads, aux)."""
    run = ref_value_and_grad_fn(c, int(batch["tokens"].shape[0]))
    with jax.default_matmul_precision("highest"):
        loss, grads = run(params, batch["tokens"], batch["labels"])
    return loss, grads, aux
