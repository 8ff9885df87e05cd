"""Ahead-of-time Mosaic compiles of the training-path Pallas kernels for a
described TPU v5e (no chip attached), at the widths the zoo's base tiers run.

The TPU compiler refuses what interpret mode accepts — block shapes whose
last two dims are not (8, 128)-tileable, 1-D operands whose HBM layout does
not match the kernel's — so these compiles guard every kernel change for the
cost of a few seconds.  Compiling proves only that the chip's compiler takes
the kernel; results and times come from a chip run (``chip_smoke.py``).

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention
from repro.kernels.fused_xent import fused_xent
from repro.kernels.ssd_scan import ssd_intra_chunk


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    cc.reset_cache()


def _specs(sharding, *shapes):
    return [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
            for shape, dtype in shapes]


def _assert_mosaic(fn, args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


# paper-transformer base tier (B=8, S=1024): 16 heads, head_dim 64, d 1024,
# vocab 32768; paper-ssm base tier (B=8, S=1024): 32 heads of 64, state 128,
# chunk 256, one group.
# flash_attention_cell: the benchmark's InternLM2 cell (B=2 x 16 heads,
# S=4096, head_dim 128) at the tiles the kernel chooses for the shape.
@pytest.mark.parametrize("kernel", ["flash_attention", "fused_xent",
                                    "ssd_intra_chunk", "flash_attention_cell"])
def test_kernel_compiles_for_v5e(kernel, one_chip, no_persistent_cache):
    bf16, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    if kernel.startswith("flash_attention"):
        qkv = ((128, 1024, 64) if kernel == "flash_attention"
               else (32, 4096, 128), bf16)
        _assert_mosaic(lambda q, k, v: flash_attention(q, k, v, causal=True,
                                                       interpret=False),
                       _specs(one_chip, qkv, qkv, qkv))
    elif kernel == "fused_xent":
        _assert_mosaic(lambda h, w, y: fused_xent(h, w, y, vocab_size=32768,
                                                  interpret=False),
                       _specs(one_chip, ((8192, 1024), bf16),
                              ((1024, 32768), bf16), ((8192,), i32)))
    else:
        N, nh, cl, hd, G, ds = 8 * 4, 32, 256, 64, 1, 128
        _assert_mosaic(lambda *a: ssd_intra_chunk(*a, interpret=False),
                       _specs(one_chip, ((N, nh, cl, hd), bf16),
                              ((N, nh, cl), f32), ((N, nh, cl), f32),
                              ((N, G, cl, ds), bf16), ((N, G, cl, ds), bf16)))
