"""The fused kernels compile for a TPU v5e chip at the shapes chip_smoke.py
runs: gemma3-1b serving widths and ResNet-18 (CIFAR-10, width 64, batch
128, crossbar 64), and ResNet-18's convs at the benchmark's batch of 256.
The chip is described, not attached (TPU compiler only, nothing runs), so
these catch what interpret mode cannot: block shapes the chip's tiling
refuses, unsupported loads, VMEM overruns.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU compiler library, and every test
worker imports this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.quant import FP32, PAPER_424
from repro.kernels import ops
from repro.models.cnn import resnet18
from repro.models.common import Ctx, LayerMode

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


def _compile_conv(sharding, batch, q8, h, cin, cout, k, s):
    dt = jnp.int8 if q8 else jnp.float32
    x = _spec((batch, h, h, cin), dt, sharding)
    w = _spec((k, k, cin, cout), dt, sharding)
    if q8:
        _compile_has_kernel(
            lambda x, w, sc: ops.cadc_conv2d_q8(
                x, w, sc, crossbar_size=64, stride=(s, s), impl="pallas"),
            x, w, _spec((), jnp.float32, sharding))
    else:
        _compile_has_kernel(
            lambda x, w: ops.cadc_conv2d(x, w, crossbar_size=64,
                                         stride=(s, s), impl="pallas"),
            x, w)


@pytest.mark.parametrize("h,cin,cout,k,s", CONVS)
def test_cadc_conv2d_fp32(one_chip, h, cin, cout, k, s):
    _compile_conv(one_chip, BATCH, False, h, cin, cout, k, s)


@pytest.mark.parametrize("h,cin,cout,k,s", CONVS)
def test_cadc_conv2d_q8(one_chip, h, cin, cout, k, s):
    _compile_conv(one_chip, BATCH, True, h, cin, cout, k, s)


# Stages 1-3 at the benchmark's batch of 256, where a grid step holds
# several images (kernels/cadc_conv.conv_block_plan)
@pytest.mark.parametrize("q8", [False, True])
@pytest.mark.parametrize("h,cin,cout,k,s", CONVS[2:])
def test_cadc_conv2d_b256(one_chip, h, cin, cout, k, s, q8):
    _compile_conv(one_chip, 256, q8, h, cin, cout, k, s)


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


# LFM2-8B-A1B's held experts (8 of 32, d 2048, expert width 1792, top-4):
# the grouped kernel at a decode step of 16 and 32 slots and at prefill
# waves of 8192 and 16384 rows, each row routed 4 ways, for the gate/up
# and the down shapes; its custom call keeps the name the roofline reads
GMM = [(rows * 4, d, n) for rows in (16, 32, 8192, 16384)
       for d, n in ((2048, 1792), (1792, 2048))]


@pytest.mark.parametrize("pairs,d,n", GMM)
def test_cadc_gmm_lfm2(one_chip, pairs, d, n):
    from repro.kernels import cadc_gmm

    bm = cadc_gmm.block_m_for(pairs, 8)
    rows = cadc_gmm.tile_bound(pairs, 8, bm) * bm
    text = jax.jit(
        lambda x, w, g: ops.cadc_gmm(x, w, g, crossbar_size=256,
                                     block_m=bm, impl="pallas")
    ).lower(_spec((rows, d), jnp.bfloat16, one_chip),
            _spec((8, d // 256, 256, n), jnp.bfloat16, one_chip),
            _spec((8,), jnp.int32, one_chip)).compile().as_text()
    assert re.search(r"%cadc_gmm_pallas[.\d]* = .*custom-call", text)


# ResNet-18 forward (width 64, crossbar 64, Pallas kernels) at batch 8: the
# custom calls keep the names that the benchmark's roofline readers match,
# and the forward's named scopes reach every instruction the chip runs
RESNET_KERNELS = {False: ("_conv_jit", "cadc_matmul_pallas"),
                  True: ("_conv_q8_jit", "cadc_matmul_q8_pallas")}
# name-stack words JAX writes itself; components with "(" are transforms
JAX_WORDS = {"while", "body", "cond", "closed_call"}
# instructions with no device work of their own
NO_WORK = {"parameter", "constant", "bitcast", "tuple", "get-tuple-element"}
CONV_LAYER = re.compile(r"stem/stem|s[0-3]b[01]/(conv1|conv2|proj)")


@pytest.fixture(scope="module")
def resnet18_hlo(one_chip):
    """{q8: compiled text of the forward} for fp32 and the 4/2/4b path."""
    p, s = jax.eval_shape(lambda k: resnet18.init(k, width=64),
                          jax.random.PRNGKey(0))
    p, s = jax.tree_util.tree_map(
        lambda a: _spec(a.shape, a.dtype, one_chip), (p, s))
    x = _spec((8, 32, 32, 3), jnp.float32, one_chip)
    out = {}
    for q8 in (False, True):
        mode = LayerMode(impl="cadc", crossbar_size=64, kernel="pallas",
                         quant=PAPER_424 if q8 else FP32, q8_fused=q8)
        out[q8] = jax.jit(
            lambda p, s, x: resnet18.apply(p, s, x, Ctx(mode))[0]
        ).lower(p, s, x).compile().as_text()
    return out


def _entry_ops(text):
    """(name, opcode, op name, operands) of each instruction of the entry
    computation. A fusion's op name is its fused computation's root's
    where the root has one; an op name that is only an argument's name
    (the layout copy of an argument) counts as none."""
    comps, cur = {}, None
    for line in text.splitlines():
        m = re.match(r"^(ENTRY )?%(\S+) .*\{$", line)
        if m:
            cur = "ENTRY" if m.group(1) else m.group(2)
            comps[cur] = []
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            m = re.match(r"^\s+(ROOT )?%(\S+) = .*?\s([a-z][a-z0-9-]*)"
                         r"\(([^)]*)\)", line)
            if m:
                name = re.search(r'op_name="([^"]*/[^"]*)"', line)
                calls = re.search(r"calls=%([\w.\-]+)", line)
                comps[cur].append((m.group(2), m.group(3),
                                   name.group(1) if name else None,
                                   re.findall(r"%([\w.\-]+)", m.group(4)),
                                   calls.group(1) if calls else None,
                                   bool(m.group(1))))
    out = []
    for name, op, op_name, args, calls, _ in comps["ENTRY"]:
        if op == "fusion":
            op_name = next(n for _, _, n, _, _, root in comps[calls]
                           if root) or op_name
        out.append((name, op, op_name, args))
    return out


def _scope(op_name):
    parts = op_name.split(";")[0].split("/")[:-1]
    return "/".join(p for p in parts if p not in JAX_WORDS and "(" not in p)


@pytest.mark.parametrize("q8", [False, True])
def test_resnet18_kernel_names(resnet18_hlo, q8):
    conv, fc = RESNET_KERNELS[q8]
    calls = [n for n, op, _, _ in _entry_ops(resnet18_hlo[q8])
             if op == "custom-call"]
    assert len([n for n in calls if re.fullmatch(rf"{conv}\.\d+", n)]) == 20
    assert len([n for n in calls if re.fullmatch(rf"{fc}\.\d+", n)]) == 1


@pytest.mark.parametrize("q8", [False, True])
def test_resnet18_conv_scopes(resnet18_hlo, q8):
    conv = RESNET_KERNELS[q8][0]
    scopes = [_scope(op_name) for n, _, op_name, _ in
              _entry_ops(resnet18_hlo[q8]) if n.startswith(conv + ".")]
    assert len(set(scopes)) == 20
    assert all(CONV_LAYER.fullmatch(s) for s in scopes), scopes


@pytest.mark.parametrize("q8", [False, True])
def test_resnet18_every_op_has_a_scope(resnet18_hlo, q8):
    """Each instruction the chip runs carries a scope of the forward, or,
    made by the compiler without an op name (a layout copy, an async copy
    or slice of a weight, the concatenation of its slices), feeds one
    that does: the rule by which the benchmark charges it."""
    ops = _entry_ops(resnet18_hlo[q8])
    users = {}
    for n, _, _, args in ops:
        for a in args:
            users.setdefault(a, []).append(n)
    scope = {n: _scope(op_name) if op_name else None
             for n, _, op_name, _ in ops}

    def charged(n):
        if scope[n] is None:
            return any(charged(u) for u in users.get(n, []))
        return bool(scope[n])

    bare = [n for n, op, _, _ in ops if op not in NO_WORK and not charged(n)]
    assert not bare
