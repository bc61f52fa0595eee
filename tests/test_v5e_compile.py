"""The fused kernels compile for a TPU v5e chip at the shapes chip_smoke.py
runs: gemma3-1b serving widths and ResNet-18 (CIFAR-10, width 64, batch
128, crossbar 64). The chip is described, not attached (TPU compiler only,
nothing runs), so these catch what interpret mode cannot: block shapes the
chip's tiling refuses, unsupported loads, VMEM overruns.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU compiler library, and every test
worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

# gemma3-1b CADC linears (contraction padded to the 256 crossbar) at decode
# (M = 8 slots) and prefill (M = 4096 tokens) rows
MATMULS = [(m, d, n) for m in (8, 4096)
           for d, n in ((1280, 1024), (1280, 256), (1024, 1152),
                        (1280, 6912), (6912, 1152))]
# ResNet-18 (CIFAR) convs: (H, Cin, Cout, k, stride)
CONVS = [(32, 3, 64, 3, 1), (32, 64, 64, 3, 1), (32, 64, 128, 3, 2),
         (32, 64, 128, 1, 2), (16, 128, 128, 3, 1), (16, 128, 256, 3, 2),
         (16, 128, 256, 1, 2), (8, 256, 256, 3, 1), (8, 256, 512, 3, 2),
         (8, 256, 512, 1, 2), (4, 512, 512, 3, 1)]
BATCH = 128


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile_has_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("m,d,n", MATMULS)
def test_cadc_matmul_bf16(one_chip, m, d, n):
    _compile_has_kernel(
        lambda x, w: ops.cadc_matmul(x, w, crossbar_size=256, impl="pallas"),
        _spec((m, d), jnp.bfloat16, one_chip),
        _spec((d, n), jnp.bfloat16, one_chip))


@pytest.mark.parametrize("kind,ring", [("local", 512), ("global", 576)])
def test_paged_attention_gemma3(one_chip, kind, ring):
    slots, bs, nb = 8, 16, ring // 16
    pool = _spec((slots * nb, bs, 1, 256), jnp.bfloat16, one_chip)
    _compile_has_kernel(
        lambda q, k, v, t, p: ops.paged_attention(
            q, k, v, t, p, kind=kind, window=512, impl="pallas"),
        _spec((slots, 1, 4, 256), jnp.bfloat16, one_chip), pool, pool,
        _spec((slots, nb), jnp.int32, one_chip),
        _spec((slots,), jnp.int32, one_chip))


@pytest.mark.parametrize("h,cin,cout,k,s", CONVS)
def test_cadc_conv2d_fp32(one_chip, h, cin, cout, k, s):
    _compile_has_kernel(
        lambda x, w: ops.cadc_conv2d(x, w, crossbar_size=64, stride=(s, s),
                                     impl="pallas"),
        _spec((BATCH, h, h, cin), jnp.float32, one_chip),
        _spec((k, k, cin, cout), jnp.float32, one_chip))


@pytest.mark.parametrize("h,cin,cout,k,s", CONVS)
def test_cadc_conv2d_q8(one_chip, h, cin, cout, k, s):
    _compile_has_kernel(
        lambda x, w, sc: ops.cadc_conv2d_q8(x, w, sc, crossbar_size=64,
                                            stride=(s, s), impl="pallas"),
        _spec((BATCH, h, h, cin), jnp.int8, one_chip),
        _spec((k, k, cin, cout), jnp.int8, one_chip),
        _spec((), jnp.float32, one_chip))


@pytest.mark.parametrize("q8", [False, True])
def test_resnet18_classifier(one_chip, q8):
    """The fc layer: [128, 512] through the 64-row crossbar."""
    dt = jnp.int8 if q8 else jnp.float32
    x = _spec((BATCH, 512), dt, one_chip)
    w = _spec((512, 10), dt, one_chip)
    if q8:
        _compile_has_kernel(
            lambda x, w, sc: ops.cadc_matmul_q8(x, w, sc, crossbar_size=64,
                                                impl="pallas"),
            x, w, _spec((), jnp.float32, one_chip))
    else:
        _compile_has_kernel(
            lambda x, w: ops.cadc_matmul(x, w, crossbar_size=64,
                                         impl="pallas"), x, w)
