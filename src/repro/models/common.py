"""Shared functional layer machinery for all models.

Every weight-bearing layer routes through `linear_forward`/`conv_forward`,
which dispatch on LayerMode.impl: 'vconv' (baseline partitioned matmul) or
'cadc' (per-crossbar dendritic f()). Quantization (4/2/4b etc.) and the ADC
noise model compose via the same mode. Psum sparsity statistics are collected
through the Ctx object (pytree-compatible — works under jit).

Each layer runs under a `jax.named_scope` of its short name (`conv1`, `fc`,
`bn1`; on the int8 path also `quantize_act` and `quantize_weight`): op-name
metadata of the compiled program, by which a profile's device time is
attributed to layers, and nothing else.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import adc as adc_lib
from repro.core import cadc as cadc_lib
from repro.core import conv as conv_lib
from repro.core import quant as quant_lib
from repro.core.quant import FP32, QuantConfig

Array = jnp.ndarray
Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class LayerMode:
    """How weight-bearing layers execute. This is the paper's experiment axis."""

    impl: str = "vconv"                 # 'vconv' | 'cadc'
    crossbar_size: int = 64             # 64 / 128 / 256 (paper sweep)
    fn: str = "relu"                    # dendritic f() for cadc
    quant: QuantConfig = FP32
    adc: Optional[adc_lib.AdcConfig] = None
    collect_stats: bool = False
    # Kernel backend for the segmented contraction: 'xla' (einsum,
    # shardable, always available), 'pallas'/'interpret'/'auto' route
    # through the fused Pallas kernels — differentiable via their
    # custom_vjp rules, so training works on any setting. Falls back to
    # the XLA path per layer when psum stats or the ADC model are
    # requested (those need materialized psums, which the fused kernel
    # never writes out).
    kernel: str = "xla"
    # Gradient-residual format of the fused kernels: 'auto' (bit-packed
    # uint32 gate bitmask for indicator fns, byte gate otherwise) |
    # 'packed' | 'bytes' | 'recompute' (no residual — the backward
    # re-derives the gate on the MXU). See kernels/cadc_matmul.py.
    save_gate: str = "auto"
    # Route ternary-weight quantized layers through the int8-native fused
    # kernels (cadc_matmul_q8 / cadc_conv2d_q8): int8 codes x int8 ternary
    # codes -> int32 psums, bit-exact vs the q8 oracle. INFERENCE path —
    # the whole layer computation sits under stop_gradient (int primals
    # would get float0 anyway; the scale partials alone would be a
    # spurious "gradient"), so jax.grad through a q8_fused layer is
    # exactly zero. Training keeps the fake-quant STE floats
    # (q8_fused=False).
    q8_fused: bool = False

    def dendritic_fn(self) -> str:
        return self.fn if self.impl == "cadc" else "identity"


VCONV = LayerMode()
CADC64 = LayerMode(impl="cadc", crossbar_size=64)


class Ctx:
    """Per-forward mutable context: rng for ADC noise, psum stats sink."""

    def __init__(self, mode: LayerMode, rng: Optional[jax.Array] = None):
        self.mode = mode
        self.rng = rng
        self.stats: List[Dict[str, Array]] = []
        self._names: List[str] = []
        self._i = 0

    def next_key(self) -> Optional[jax.Array]:
        if self.rng is None:
            return None
        self._i += 1
        return jax.random.fold_in(self.rng, self._i)

    def psum_transform(self):
        if self.mode.adc is None:
            return None
        return adc_lib.make_psum_transform(self.mode.adc, self.next_key())

    def record(self, name: str, psums: Optional[Array], segments: int):
        if not self.mode.collect_stats or psums is None:
            return
        self._names.append(name)
        self.stats.append(
            {
                "sparsity": jnp.mean((psums == 0).astype(jnp.float32)),
                "count": jnp.asarray(float(psums.size // psums.shape[0]), jnp.float32),
                "segments": jnp.asarray(float(segments), jnp.float32),
            }
        )

    def stats_dict(self) -> Dict[str, Dict[str, Array]]:
        return dict(zip(self._names, self.stats))


# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------

def he_init(key, shape, fan_in, dtype=jnp.float32):
    return jax.random.normal(key, shape, dtype) * jnp.sqrt(2.0 / fan_in)


def dense_init(key, d_in, d_out, *, bias=True, dtype=jnp.float32) -> Params:
    p = {"w": he_init(key, (d_in, d_out), d_in, dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def conv_init(key, k1, k2, cin, cout, *, dtype=jnp.float32) -> Params:
    return {"w": he_init(key, (k1, k2, cin, cout), k1 * k2 * cin, dtype)}


# ---------------------------------------------------------------------------
# forward ops
# ---------------------------------------------------------------------------

def _use_fused(mode: LayerMode, want_ps: bool) -> bool:
    """Route through the Pallas kernels? Only when nothing needs the
    materialized psums (stats sink / ADC transform) — the fused kernel
    never writes them to HBM, which is the point. The `mode.adc is None`
    guard is LOAD-BEARING: without it, mode.kernel would silently skip
    the ADC noise model (psum_transform never reaches the fused path).
    Contract pinned by tests/test_adc_kernel_fallback.py — kernel+adc
    must be bit-identical to the xla reference with the same rng."""
    return mode.kernel != "xla" and not want_ps and mode.adc is None


def _use_q8(mode: LayerMode) -> bool:
    """Int8-native fused path: opted in, quantization on, ternary weights
    and int8-representable inputs (the paper's 4/2/4b operating point)."""
    return (mode.q8_fused and mode.quant.enabled
            and mode.quant.weight_bits == 2 and mode.quant.input_bits <= 8)


def _scope(name: str) -> str:
    """The named scope of layer `name`: its last dotted part (`s1b0.conv1`
    -> `conv1`), since the enclosing block has a scope of its own."""
    return name.rpartition(".")[2]


def _q8_operands(x: Array, w: Array, bits: int):
    """(activation codes, weight codes, shared scale) of the int8 path,
    each part under its own named scope. stop_gradient: inference-only
    path — without it jax.grad would deliver a spurious
    scale-direction-only "gradient" (int codes are float0)."""
    with jax.named_scope("quantize_act"):
        x_codes, lsb = quant_lib.quantize_codes(jax.lax.stop_gradient(x), bits)
    with jax.named_scope("quantize_weight"):
        w_codes, alpha = quant_lib.ternary_decompose(jax.lax.stop_gradient(w))
    return x_codes, w_codes, jax.lax.stop_gradient(lsb * alpha)


def linear_forward(p: Params, x: Array, ctx: Ctx, *, name: str = "fc") -> Array:
    with jax.named_scope(_scope(name)):
        return _linear(p, x, ctx, name)


def _linear(p: Params, x: Array, ctx: Ctx, name: str) -> Array:
    from repro.kernels import ops as kops

    mode = ctx.mode
    segs = cadc_lib.num_segments(p["w"].shape[0], mode.crossbar_size)
    want_ps = mode.collect_stats and segs > 1
    if _use_q8(mode) and not want_ps and mode.adc is None:
        # Int8-native crossbar arithmetic (alpha * codes == ternarize(w)):
        # one shared fp32 scale, int32 psums, bit-exact vs the q8 oracle
        # on every impl (the xla dispatch IS the oracle).
        x_codes, w_codes, scale = _q8_operands(x, p["w"],
                                               mode.quant.input_bits)
        y = kops.cadc_matmul_q8(
            x_codes, w_codes, scale,
            crossbar_size=mode.crossbar_size,
            fn=mode.dendritic_fn(), impl=mode.kernel,
            save_gate=mode.save_gate,
        ).astype(x.dtype)
        if "b" in p:
            y = y + p["b"]
        return y
    w = mode.quant.quant_weight(p["w"])
    xq = mode.quant.quant_input(x)
    if _use_fused(mode, want_ps):
        y = kops.cadc_matmul(
            xq, w, crossbar_size=mode.crossbar_size, fn=mode.dendritic_fn(),
            impl=mode.kernel, save_gate=mode.save_gate,
        )
        if "b" in p:
            y = y + p["b"]
        return y
    out = cadc_lib.cadc_matmul(
        xq,
        w,
        crossbar_size=mode.crossbar_size,
        fn=mode.dendritic_fn(),
        return_psums=want_ps,
        psum_transform=ctx.psum_transform() if segs > 1 or mode.adc else None,
    )
    if want_ps:
        y, psums = out.y, out.psums
        ctx.record(name, psums, segs)
    else:
        y = out
    if "b" in p:
        y = y + p["b"]
    return y


def conv_forward(
    p: Params,
    x: Array,
    ctx: Ctx,
    *,
    stride=(1, 1),
    padding="SAME",
    name: str = "conv",
) -> Array:
    with jax.named_scope(_scope(name)):
        return _conv(p, x, ctx, stride, padding, name)


def _conv(p: Params, x: Array, ctx: Ctx, stride, padding, name: str) -> Array:
    from repro.kernels import ops as kops

    mode = ctx.mode
    k1, k2, cin, _ = p["w"].shape
    segs = cadc_lib.num_segments(k1 * k2 * cin, mode.crossbar_size)
    want_ps = mode.collect_stats and segs > 1
    if _use_q8(mode) and not want_ps and mode.adc is None:
        # Inference-only int8 path, as in _linear.
        x_codes, w_codes, scale = _q8_operands(x, p["w"],
                                               mode.quant.input_bits)
        return kops.cadc_conv2d_q8(
            x_codes, w_codes, scale,
            crossbar_size=mode.crossbar_size,
            fn=mode.dendritic_fn(), stride=stride, padding=padding,
            impl=mode.kernel, save_gate=mode.save_gate,
        ).astype(x.dtype)
    w = mode.quant.quant_weight(p["w"])
    xq = mode.quant.quant_input(x)
    if _use_fused(mode, want_ps):
        return kops.cadc_conv2d(
            xq, w, crossbar_size=mode.crossbar_size, fn=mode.dendritic_fn(),
            stride=stride, padding=padding, impl=mode.kernel,
            save_gate=mode.save_gate,
        )
    out = conv_lib.cadc_conv2d(
        xq,
        w,
        crossbar_size=mode.crossbar_size,
        fn=mode.dendritic_fn(),
        stride=stride,
        padding=padding,
        return_psums=want_ps,
        psum_transform=ctx.psum_transform() if segs > 1 or mode.adc else None,
    )
    if want_ps:
        y, psums = out.y, out.psums
        ctx.record(name, psums, segs)
    else:
        y = out
    return y


# ---------------------------------------------------------------------------
# BatchNorm (functional, EMA state threaded)
# ---------------------------------------------------------------------------

def bn_init(c: int) -> Tuple[Params, Params]:
    params = {"scale": jnp.ones((c,)), "bias": jnp.zeros((c,))}
    state = {"mean": jnp.zeros((c,)), "var": jnp.ones((c,))}
    return params, state


def bn_forward(
    p: Params, s: Params, x: Array, *, train: bool, momentum: float = 0.9,
    name: str = "bn",
) -> Tuple[Array, Params]:
    with jax.named_scope(name):
        return _bn(p, s, x, train, momentum)


def _bn(p: Params, s: Params, x: Array, train: bool, momentum: float
        ) -> Tuple[Array, Params]:
    axes = tuple(range(x.ndim - 1))
    if train:
        mean = jnp.mean(x, axes)
        var = jnp.var(x, axes)
        new_s = {
            "mean": momentum * s["mean"] + (1 - momentum) * mean,
            "var": momentum * s["var"] + (1 - momentum) * var,
        }
    else:
        mean, var = s["mean"], s["var"]
        new_s = s
    y = (x - mean) * jax.lax.rsqrt(var + 1e-5) * p["scale"] + p["bias"]
    return y, new_s


def max_pool(x: Array, window=2, stride=2) -> Array:
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max,
        (1, window, window, 1), (1, stride, stride, 1), "VALID",
    )


def avg_pool(x: Array, window=2, stride=2) -> Array:
    s = jax.lax.reduce_window(
        x, 0.0, jax.lax.add,
        (1, window, window, 1), (1, stride, stride, 1), "VALID",
    )
    return s / (window * window)


def global_avg_pool(x: Array) -> Array:
    return jnp.mean(x, axis=(1, 2))
