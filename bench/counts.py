"""Operations and bytes each measured piece of work requires, computed from
shapes. A count is the work the call needs, never more: recomputation,
padding and masked lanes are left out, so a share built on these counts
cannot pass 100% unless the timing misses part of the work.

Conventions: a matrix product (m, k) x (k, n) is 2*m*k*n operations; bytes
are the operands read and the results written, at their dtypes.
"""
from __future__ import annotations

from typing import Mapping, Sequence

BF16 = 2
F32 = 4


# ------------------------------------------------------------ language ----
def lm_shape(c: Mapping) -> dict:
    """Sizes of a decoder-only configuration file (Hugging Face key names)."""
    d = int(c["hidden_size"])
    h = int(c["num_attention_heads"])
    return {"layers": int(c["num_hidden_layers"]), "d": d, "heads": h,
            "kv": int(c["num_key_value_heads"]),
            "head_dim": int(c.get("head_dim", d // h)),
            "ff": int(c["intermediate_size"]), "vocab": int(c["vocab_size"])}


def lm_matmul_params(c: Mapping) -> int:
    """Weights that enter a matrix product once per token: q, k, v, o and
    the gated MLP in every layer, and the output head."""
    s = lm_shape(c)
    attn = s["d"] * s["head_dim"] * (2 * s["heads"] + 2 * s["kv"])
    mlp = 3 * s["d"] * s["ff"]
    return s["layers"] * (attn + mlp) + s["vocab"] * s["d"]


def causal_pairs(seq: int) -> float:
    """(query, key) pairs a causal row of ``seq`` tokens attends."""
    return seq * (seq + 1) / 2.0


def lm_fwd_flops(c: Mapping, tokens: float, pairs: float) -> float:
    """Forward operations for ``tokens`` tokens that attend ``pairs``
    (query, key) pairs in all: matmuls plus the two attention products."""
    s = lm_shape(c)
    return (2.0 * lm_matmul_params(c) * tokens
            + 4.0 * s["layers"] * s["heads"] * s["head_dim"] * pairs)


def lm_train_flops_per_sample(c: Mapping, seq: int) -> float:
    """Forward plus backward (2x forward) of one causal row; no
    recomputation counted."""
    return 3.0 * lm_fwd_flops(c, seq, causal_pairs(seq))


def flash_train_flops(batch: int, heads: int, head_dim: int, seq: int,
                      layers: int = 1) -> float:
    """Causal attention forward (QK^T, PV) and backward (QK^T again, since
    the kernel keeps no probabilities, then dP, dV, dQ, dK): 7 products of
    2*D operations per causal pair and head."""
    return 14.0 * batch * heads * head_dim * causal_pairs(seq) * layers


def flash_train_bytes(batch: int, heads: int, kv: int, head_dim: int,
                      seq: int, layers: int = 1) -> float:
    """Forward reads q, k, v and writes o and the f32 log-sum-exp; backward
    reads q, k, v, o, dO and the log-sum-exp and writes dQ, dK, dV."""
    q = batch * seq * heads * head_dim * BF16
    kv_ = batch * seq * kv * head_dim * BF16
    lse = batch * seq * heads * F32
    fwd = q + 2 * kv_ + q + lse
    bwd = q + 2 * kv_ + 2 * q + lse + q + 2 * kv_
    return float((fwd + bwd) * layers)


def decode_kv_bytes(lengths: Sequence[int], layers: int, heads: int,
                    kv: int, head_dim: int, dtype_bytes: int = BF16) -> float:
    """One ragged decode step over rows of the given live lengths: every
    layer reads each row's live keys and values and its query, and writes
    its output."""
    live = sum(int(n) for n in lengths)
    rows = len(lengths)
    kv_b = 2 * live * kv * head_dim * dtype_bytes
    q_o = 2 * rows * heads * head_dim * dtype_bytes
    return float(layers * (kv_b + q_o))


# ------------------------------------------------------------- vision -----
RESNET18_STAGES = ((64, 2, 1), (128, 2, 2), (256, 2, 2), (512, 2, 2))


def resnet18_fwd_flops(c: Mapping) -> float:
    """One image through the CIFAR ResNet-18 (3x3 stem, four stages of two
    basic blocks, 1x1 projection where the shape changes, linear head):
    convolution and head products only."""
    s = int(c["image_size"]) // int(c["stem_stride"])
    ch = int(c["in_channels"])
    macs = s * s * 9 * ch * 64
    cin = 64
    for cout, blocks, stride in RESNET18_STAGES:
        for b in range(blocks):
            st = stride if b == 0 else 1
            s_out = s // st
            macs += s_out * s_out * 9 * cin * cout          # conv1
            macs += s_out * s_out * 9 * cout * cout         # conv2
            if st != 1 or cin != cout:
                macs += s_out * s_out * cin * cout          # projection
            cin, s = cout, s_out
    macs += cin * int(c["num_classes"])
    return 2.0 * macs


def resnet18_train_flops_per_sample(c: Mapping) -> float:
    return 3.0 * resnet18_fwd_flops(c)


# ------------------------------------------------------- fused update -----
def fused_update_bytes(elements: int, grad_bytes: int, compute_bytes: int,
                       moments: int = 1) -> float:
    """The bytes the update must move for ``elements`` parameters: the
    gradient read once, the f32 master and each f32 moment read and
    written, and the next step's compute copy written. (The program reads
    the gradient twice, once per sweep, and sweeps the slab's padding
    rows too; both are costs of its design, not of the update.)"""
    return float(elements * (grad_bytes + 2 * F32 * (1 + moments)
                             + compute_bytes))
