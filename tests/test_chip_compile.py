"""Real-width compiles of the main-path Pallas kernels for a described TPU
v5e chip: no chip is needed, only the TPU compiler, which refuses what
interpret mode accepts (block shapes off the (8, 128) tile, scalars stored
to VMEM, casts Mosaic cannot lower). Shapes are smollm-135m's: head dim 64,
9 query / 3 kv heads, seq 2048, and its (rows, 512) update slab; flash
training is also compiled at recurrentgemma-2b's head dim 256 (10 heads,
1 kv head, window 2048) and deepseek-v2-lite's split MLA dims (16 heads,
qk 192, v 128), and the forward at the 32k-token prefill, whose tile table
is the largest any configuration puts in SMEM.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every xdist worker imports this
file."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import flash_attention as fa
from repro.kernels import fused_update as fu
from repro.kernels import qdq_cast as qc
from repro.kernels.layout import SLAB_M, SLAB_N

B, S, H, K, D = 4, 2048, 9, 3, 64       # smollm-135m at rung 4
DECODE_B, CACHE_L = 8, 512


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # noqa: BLE001 — any failure means skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()


def _slab_rows():
    """Rows of smollm-135m's update slab (shapes only, nothing allocated)."""
    from repro.kernels.layout import slab_view
    from repro.models.registry import get_task
    task = get_task("smollm-135m")
    wrapped, _ = jax.eval_shape(task.init, jax.ShapeDtypeStruct(
        (2,), jnp.uint32))
    params = jax.tree.map(lambda p: p.value, wrapped,
                          is_leaf=lambda x: hasattr(x, "axes"))
    grouping = task.grouping(params)
    return slab_view(params, grouping).rows, grouping.num_layers


def _flash_fwd():
    q = ((B, S, H, D), jnp.bfloat16)
    kv = ((B, S, K, D), jnp.bfloat16)
    return lambda q, k, v: fa.flash_attention_fwd(q, k, v), [q, kv, kv]


def _flash_bwd():
    q = ((B, S, H, D), jnp.bfloat16)
    kv = ((B, S, K, D), jnp.bfloat16)
    lse = ((B, H, 1, S), jnp.float32)
    return (lambda q, k, v, o, l, do: fa.flash_attention_bwd(q, k, v, o, l,
                                                             do),
            [q, kv, kv, q, lse, q])


def _flash_train(H, K, D, Dv, window=0):
    """Forward with the lse residual and both backward kernels."""
    def fwd_bwd(q, k, v, do):
        o, lse = fa.flash_attention_fwd(q, k, v, window=window)
        return fa.flash_attention_bwd(q, k, v, o, lse, do, window=window)
    return fwd_bwd, [((1, S, H, D), jnp.bfloat16),
                     ((1, S, K, D), jnp.bfloat16),
                     ((1, S, K, Dv), jnp.bfloat16),
                     ((1, S, H, Dv), jnp.bfloat16)]


def _flash_train_hd256():
    return _flash_train(10, 1, 256, 256, window=2048)


def _flash_train_mla():
    return _flash_train(16, 16, 192, 128)


def _flash_prefill_32k():
    """The longest sequence any configuration runs the kernels at
    (prefill_32k): the causal tile table, 8,256 words, must fit in SMEM."""
    q = ((1, 32768, H, D), jnp.bfloat16)
    kv = ((1, 32768, K, D), jnp.bfloat16)
    return lambda q, k, v: fa.flash_attention(q, k, v), [q, kv, kv]


def _flash_decode():
    kv = ((DECODE_B, CACHE_L, K, D), jnp.bfloat16)
    return (lambda q, k, v, n: fa.flash_decode(q, k, v, n),
            [((DECODE_B, 1, H, D), jnp.bfloat16), kv, kv,
             ((DECODE_B,), jnp.int32)])


def _fused_stats():
    rows, L = _slab_rows()
    return (lambda g, rl: fu.fused_stats(g, rl, L),
            [((rows, SLAB_N), jnp.bfloat16),
             ((rows // SLAB_M, SLAB_M), jnp.int32)])


def _fused_apply():
    rows, L = _slab_rows()
    meta = (rows // SLAB_M, SLAB_M)

    def apply(g, p, m, sc, rl, lr, code, qs):
        return fu.fused_apply(g, p, m, None, sc, rl, lr, code, qs,
                              spec=fu.OptSpec("sgdm"), ladder="tpu",
                              cp_dtype=jnp.bfloat16, num_layers=L, sr=True)
    return apply, [((rows, SLAB_N), jnp.bfloat16),
                   ((rows, SLAB_N), jnp.float32),
                   ((rows, SLAB_N), jnp.float32), ((5,), jnp.float32),
                   (meta, jnp.int32), (meta, jnp.float32),
                   (meta, jnp.int32), (meta, jnp.float32)]


def _qdq_cast():
    # the tied embedding, the largest tier-0 serving weight
    return (lambda x, c: qc.qdq_cast(x, c, ladder="tpu"),
            [((49152, 576), jnp.float32), ((), jnp.int32)])


@pytest.mark.parametrize("build", [_flash_fwd, _flash_bwd, _flash_train_hd256,
                                   _flash_train_mla, _flash_prefill_32k,
                                   _flash_decode,
                                   _fused_stats, _fused_apply, _qdq_cast],
                         ids=lambda f: f.__name__.lstrip("_"))
def test_kernel_compiles_for_v5e(one_chip, build):
    fn, shapes = build()
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
