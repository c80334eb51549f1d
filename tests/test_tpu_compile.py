"""Compile-only checks for a TPU v5e: the Pallas kernels at model widths,
and the image kernels of the IMG program at its published size.

Interpret mode (test_kernels.py) cannot see what the chip's compiler
refuses: blocks that break the (8, 128) tiling rule, more VMEM than a
kernel may use, or a program larger than the device's HBM.  These tests
lower each kernel for one chip of a described ``v5e:2x2`` topology --
nothing runs -- and check that a Pallas kernel's program holds the Mosaic
kernel (``tpu_custom_call``), not an interpreted loop.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, so describing it at
collection time would make the workers of a parallel run disagree about
which tests exist.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.benchsuite import BENCHMARKS
from repro.benchsuite import kernels as K
from repro.kernels.flash_attention.kernel import flash_attention_bhsd
from repro.kernels.rmsnorm.kernel import rmsnorm_2d
from repro.kernels.rwkv6.kernel import wkv6_bh


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip; keep the cache out of it.
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_flash_attention_compiles_at_hymba_widths(one_chip):
    # Hymba-1.5B prefill: 25 query heads over 5 KV heads, hd 64, SWA 1024.
    S, hd = 2048, 64
    q = _spec((25, S, hd), jnp.bfloat16, one_chip)
    kv = _spec((5, S, hd), jnp.bfloat16, one_chip)
    compiled = flash_attention_bhsd.lower(
        q, kv, kv, causal=True, window=1024, num_q_heads=25).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("d", [1600, 2048])
def test_rmsnorm_compiles_at_model_widths(one_chip, d):
    # d_model of Hymba-1.5B (1600) and RWKV-6-1.6B (2048), 4096 tokens.
    x = _spec((4096, d), jnp.bfloat16, one_chip)
    scale = _spec((d,), jnp.bfloat16, one_chip)
    compiled = rmsnorm_2d.lower(x, scale).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_wkv6_compiles_at_rwkv6_widths(one_chip):
    # RWKV-6-1.6B: 32 heads of 64 channels, one sequence of 1024 tokens.
    BH, T, hd = 32, 1024, 64
    seq = _spec((BH, T, hd), jnp.bfloat16, one_chip)
    u = _spec((BH, hd), jnp.bfloat16, one_chip)
    s0 = _spec((BH, hd, hd), jnp.float32, one_chip)
    compiled = wkv6_bh.lower(seq, seq, seq, seq, u, s0).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("kernel", ["blur3", "blur13", "sobel", "extend"])
def test_img_kernels_fit_one_chip_at_published_size(one_chip, kernel):
    # A one-channel lax.conv here once asked for 49 GB of HBM: its size-1
    # feature dim is padded 128-fold by the TPU tiling.  The compiler
    # refuses a program that does not fit the device.
    s = BENCHMARKS["IMG"].sizes(1.0)
    img = _spec((s["h"], s["w"]), jnp.float32, one_chip)
    fn, kw = {"blur3": (K.k_gaussian_blur, dict(ksize=3, sigma=1.0)),
              "blur13": (K.k_gaussian_blur, dict(ksize=13, sigma=5.0)),
              "sobel": (K.k_sobel, {}),
              "extend": (K.k_extend_mask, {})}[kernel]
    mem = fn.lower(img, img, **kw).compile().memory_analysis()
    assert mem.temp_size_in_bytes < 4 * img.size * 4
