"""Operation and byte counts against hand counts."""
import itertools
import json
import os

import pytest

from bench import counts

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs")


def load(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


def test_smollm_flops_per_token():
    c = load("smollm-135m")
    # q, k, v, o: 576*64*(9+3+3+9); MLP 3*576*1536; 30 layers; head 49152*576
    per_layer = 576 * 64 * 24 + 3 * 576 * 1536
    params = 30 * per_layer + 49152 * 576
    assert counts.lm_matmul_params(c) == params
    assert 6 * params == pytest.approx(807e6, rel=2e-3)
    seq = 2048
    attn = counts.lm_train_flops_per_sample(c, seq) / seq - 6 * params
    # 3 x 4 * layers * heads * head_dim * (seq + 1) / 2 per token
    assert attn == pytest.approx(3 * 4 * 30 * 9 * 64 * (seq + 1) / 2)
    assert attn == pytest.approx(212e6, rel=3e-3)


def test_resnet18_cifar_flops_per_image():
    # the paper's CIFAR-10 ResNet-18 (arXiv:2508.16905)
    c = {"image_size": 32, "stem_stride": 1, "in_channels": 3,
         "num_classes": 10}
    macs = (32 * 32 * 27 * 64                         # stem
            + 4 * 32 * 32 * 9 * 64 * 64               # stage 0
            + 16 * 16 * (9 * 64 * 128 + 3 * 9 * 128 * 128 + 64 * 128)
            + 8 * 8 * (9 * 128 * 256 + 3 * 9 * 256 * 256 + 128 * 256)
            + 4 * 4 * (9 * 256 * 512 + 3 * 9 * 512 * 512 + 256 * 512)
            + 512 * 10)
    assert counts.resnet18_fwd_flops(c) == 2 * macs
    assert counts.resnet18_train_flops_per_sample(c) == pytest.approx(
        3.34e9, rel=0.01)


@pytest.mark.parametrize("seq", [1, 2, 5, 8])
def test_flash_causal_flops_count_each_pair(seq):
    B, H, D, L = 2, 3, 4, 5
    pairs = sum(1 for q, k in itertools.product(range(seq), repeat=2)
                if k <= q)
    # forward 2 products, backward 5 (scores again, dP, dV, dQ, dK)
    want = (2 + 5) * 2 * D * pairs * B * H * L
    assert counts.flash_train_flops(B, H, D, seq, L) == want


def test_flash_bytes_are_operands_and_results():
    B, H, K, D, S = 1, 2, 1, 4, 8
    q, kv, lse = B * S * H * D * 2, B * S * K * D * 2, B * S * H * 4
    fwd = q + 2 * kv + q + lse                      # q,k,v in; o, lse out
    bwd = (q + 2 * kv + q + q + lse) + (q + 2 * kv)  # q,k,v,o,dO,lse in
    assert counts.flash_train_bytes(B, H, K, D, S) == fwd + bwd


def test_fused_update_slab_bytes():
    n = 134_515_008
    # bf16 gradient read once; f32 master and momentum read and written;
    # bf16 compute copy written
    assert counts.fused_update_bytes(n, 2, 2) == n * (2 + 8 + 8 + 2)
    # float32 compute (the vision testbed)
    assert counts.fused_update_bytes(n, 4, 4) == n * (4 + 8 + 8 + 4)
    # Adam's second moment: one more f32 slab read and written
    assert counts.fused_update_bytes(n, 2, 2, moments=2) == \
        n * (2 + 12 + 12 + 2)


def test_decode_kv_bytes_at_live_lengths():
    lengths = [1, 5, 128]
    layers, H, K, D = 30, 9, 3, 64
    kv = 2 * sum(lengths) * K * D * 2
    qo = 2 * len(lengths) * H * D * 2
    assert counts.decode_kv_bytes(lengths, layers, H, K, D) == \
        layers * (kv + qo)
