"""Pallas TPU kernel: fused im2col + CADC segmented conv2d (+ q8 variant).

TPU adaptation (DESIGN.md §2, §6): the paper's crossbar pipeline for conv is
im2col-unroll -> crossbar psums -> IMA f() -> accumulate. The XLA fallback
(core/conv.py) materializes patches and psums; this kernel keeps BOTH in
VMEM:

  * the (padded) feature map tile stays VMEM-resident — CNN-scale fmaps
    (paper's largest: 32x32x512 fp32 = 2 MB) fit comfortably;
  * patches are sliced out of the fmap inside the kernel (static tap loop,
    dynamic row offset) — im2col is never written to HBM;
  * each crossbar segment's psum tile lives in VREGs, f() applied in place,
    accumulated into a VMEM scratch tile (the IMA + psum-adder of the
    paper), written to the output block ONCE.

Segmentation is EXACT w.r.t. the reference: the unrolled D = K1*K2*C axis
(taps outer, channels fastest — core/conv.py order) is cut into S = ceil(D/N)
contiguous crossbar segments; a segment may span several taps, handled by a
static python loop over the intersecting taps with psum accumulated BEFORE
f() — bit-identical grouping to cadc_conv2d.

Grid: (B, OH/bh, Cout/bn), all parallel — the segment loop runs INSIDE the
kernel body over a VMEM scratch accumulator (no S grid axis, no O(S)
pl.when dispatch chain, no output revisits). x block = the stride phases
of one padded image [1, s1*s2, HQ, WQ, C]; w block = [D, bn] column slice;
out block = [1, bh, OW, bn] written exactly once.

Constraints: dilation=1; strides are split into input phases outside the
kernel (_stride_phases) so every in-kernel read is unit-stride; the padded
image must fit VMEM (wrapper falls back to the im2col XLA path otherwise —
see ops.cadc_conv2d).

Quantized variant (cadc_conv2d_q8_pallas)
-----------------------------------------
The paper's 4/2/4b operating point int8-native: int8 activation taps x int8
ternary codes -> int32 segment psums on the MXU -> dequant by the shared
fp32 scale (input_lsb * weight_alpha) -> f() -> fp32 accumulate. Per-tap
int32 adds are associative, so the kernel is bit-exact against the
sequential q8 oracle (kernels/ref.cadc_conv2d_q8_ref).

Gradients (custom_vjp)
----------------------
Because the conv IS the segmented matmul over im2col patches, its VJP
reuses the segmented backward Pallas kernels of cadc_matmul:

  forward:  for save_gate in {"auto","packed","bytes"} emits the
            per-segment gate f'(psum) as a second kernel output while the
            psum tile is in VREGs — lane-packed uint32 bitmask words for
            indicator gates ([S, B, OH, OW, Cout/32], 8x less residual HBM
            than the byte-bool), or one gate_dtype element per psum.
            save_gate="recompute" saves NOTHING;
  backward: recomputes patches via the cheap XLA im2col (a dozen strided
            slices), runs dpatches = (g ⊙ gate_s) @ w_sᵀ and
            dw_s = patchesᵀ @ (g ⊙ gate_s) as the SAME (parallel, parallel,
            arbitrary) segmented MXU kernels (unpacking the bitmask — or
            re-deriving the gate from one extra MXU matmul in recompute
            mode), then folds dpatches back to dx with a static col2im
            scatter-add (linear, XLA).

The two heavy contractions — all the FLOPs of the backward — thus run on
the MXU with psum-free residuals; only the O(K^2) fold is left to XLA.
The q8 conv gets the same straight-through VJP as cadc_matmul_q8: int
primals get float0 cotangents, d(scale) = <dw_unscaled, w>.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import dendritic
from repro.core.conv import _norm_padding, im2col
from repro.kernels.cadc_matmul import (GATE_PACK_WIDTH, _float0_zeros,
                                       _pack_mask, _resolve_gate,
                                       _resolve_gate_mode, _segmented_bwd)

Array = jnp.ndarray


def _segment_taps(k1: int, k2: int, c: int, xbar: int):
    """For each segment s: list of (tap_i, tap_j, c_lo, c_sz, d_off) where
    d_off is the row offset inside the segment's xbar-row window."""
    d = k1 * k2 * c
    n_seg = -(-d // xbar)
    segs = []
    for s in range(n_seg):
        lo, hi = s * xbar, min((s + 1) * xbar, d)
        taps = []
        t0, t1 = lo // c, (hi - 1) // c
        for t in range(t0, t1 + 1):
            i, j = divmod(t, k2)
            c_lo = max(lo - t * c, 0)
            c_hi = min(hi - t * c, c)
            taps.append((i, j, c_lo, c_hi - c_lo, t * c + c_lo - lo))
        segs.append(taps)
    return segs


def _tap_psum(x_ref, w_ref, taps, *, oh0, bh, ow, s1, s2, xbar, bn, si,
              acc_dtype=jnp.float32):
    """Accumulate one segment's psum tile [bh*ow, bn] over its taps.

    x_ref holds the stride phases of the padded image (_stride_phases):
    tap (i, j) of output pixel (r, q) lives in phase (i % s1, j % s2) at
    (r + i // s1, q + j // s2), so every read is unit-stride — Mosaic
    refuses strided loads of sub-32-bit data and gathers from a loaded
    value. acc_dtype=int32 gives the exact integer psums of the q8 path
    (int8 operands straight into the MXU)."""
    p = jnp.zeros((bh * ow, bn), acc_dtype)
    for (i, j, c_lo, c_sz, d_off) in taps:
        xt = x_ref[0, (i % s1) * s2 + j % s2, pl.ds(oh0 + i // s1, bh),
                   j // s2:j // s2 + ow, c_lo:c_lo + c_sz]
        wt = w_ref[si * xbar + d_off : si * xbar + d_off + c_sz, :]
        p += jnp.dot(xt.reshape(bh * ow, c_sz), wt,
                     preferred_element_type=acc_dtype)
    return p


def _kernel(x_ref, w_ref, o_ref, acc_ref, *, fn: Callable, segs, bh: int,
            ow: int, s1: int, s2: int, xbar: int, bn: int):
    oh0 = pl.program_id(1) * bh  # first phase row of this row block
    for si, taps in enumerate(segs):
        p = _tap_psum(x_ref, w_ref, taps, oh0=oh0, bh=bh, ow=ow, s1=s1,
                      s2=s2, xbar=xbar, bn=bn, si=si)
        fps = fn(p)
        if si == 0:
            acc_ref[...] = fps
        else:
            acc_ref[...] += fps
    o_ref[...] = acc_ref[...].reshape(1, bh, ow, bn)


def _kernel_with_gate(x_ref, w_ref, o_ref, g_ref, acc_ref, *, fn: Callable,
                      gate_fn: Callable, segs, bh: int, ow: int, s1: int,
                      s2: int, xbar: int, bn: int, packed: bool):
    """VJP forward: also writes each segment's gate f'(psum) tile."""
    oh0 = pl.program_id(1) * bh
    for si, taps in enumerate(segs):
        p = _tap_psum(x_ref, w_ref, taps, oh0=oh0, bh=bh, ow=ow, s1=s1,
                      s2=s2, xbar=xbar, bn=bn, si=si)
        gate = gate_fn(p)
        if packed:
            g_ref[si] = _pack_mask(gate).reshape(
                1, bh, ow, bn // GATE_PACK_WIDTH)
        else:
            g_ref[si] = gate.astype(g_ref.dtype).reshape(1, bh, ow, bn)
        fps = fn(p)
        if si == 0:
            acc_ref[...] = fps
        else:
            acc_ref[...] += fps
    o_ref[...] = acc_ref[...].reshape(1, bh, ow, bn)


def _q8_kernel(x_ref, w_ref, scale_ref, o_ref, acc_ref, *, fn: Callable,
               segs, bh: int, ow: int, s1: int, s2: int, xbar: int, bn: int):
    """int8 taps x int8 ternary codes -> int32 segment psum -> dequant ->
    f() -> fp32 accumulate. scale_ref is (1,1) fp32."""
    oh0 = pl.program_id(1) * bh
    for si, taps in enumerate(segs):
        p_i32 = _tap_psum(x_ref, w_ref, taps, oh0=oh0, bh=bh, ow=ow, s1=s1,
                          s2=s2, xbar=xbar, bn=bn, si=si,
                          acc_dtype=jnp.int32)
        fps = fn(p_i32.astype(jnp.float32) * scale_ref[0, 0])
        if si == 0:
            acc_ref[...] = fps
        else:
            acc_ref[...] += fps
    o_ref[...] = acc_ref[...].reshape(1, bh, ow, bn)


def _q8_kernel_with_gate(x_ref, w_ref, scale_ref, o_ref, g_ref, acc_ref, *,
                         fn: Callable, gate_fn: Callable, segs, bh: int,
                         ow: int, s1: int, s2: int, xbar: int, bn: int,
                         packed: bool):
    oh0 = pl.program_id(1) * bh
    for si, taps in enumerate(segs):
        p_i32 = _tap_psum(x_ref, w_ref, taps, oh0=oh0, bh=bh, ow=ow, s1=s1,
                          s2=s2, xbar=xbar, bn=bn, si=si,
                          acc_dtype=jnp.int32)
        psum = p_i32.astype(jnp.float32) * scale_ref[0, 0]
        gate = gate_fn(psum)
        if packed:
            g_ref[si] = _pack_mask(gate).reshape(
                1, bh, ow, bn // GATE_PACK_WIDTH)
        else:
            g_ref[si] = gate.astype(g_ref.dtype).reshape(1, bh, ow, bn)
        fps = fn(psum)
        if si == 0:
            acc_ref[...] = fps
        else:
            acc_ref[...] += fps
    o_ref[...] = acc_ref[...].reshape(1, bh, ow, bn)


def _col2im(
    dp: Array,
    x_shape: Tuple[int, int, int, int],
    kernel: Tuple[int, int],
    stride: Tuple[int, int],
    padding,
) -> Array:
    """Adjoint of core.conv.im2col (dilation=1): scatter-add each tap's
    dpatch slice back onto the padded image, then crop the conv padding."""
    k1, k2 = kernel
    s1, s2 = stride
    b, h, w, c = x_shape
    (pt, pb), (pl_, pr) = _norm_padding(padding, kernel, (1, 1))
    hp, wp = h + pt + pb, w + pl_ + pr
    oh, ow = dp.shape[1], dp.shape[2]
    dp5 = dp.reshape(b, oh, ow, k1 * k2, c)
    dx = jnp.zeros((b, hp, wp, c), dp.dtype)
    for i in range(k1):
        for j in range(k2):
            dx = dx.at[
                :, i : i + (oh - 1) * s1 + 1 : s1,
                j : j + (ow - 1) * s2 + 1 : s2, :,
            ].add(dp5[:, :, :, i * k2 + j, :])
    return dx[:, pt : pt + h, pl_ : pl_ + w, :]


def _stride_phases(x, pad_h, pad_w, stride, hq, wq):
    """Zero-pad x [B, H, W, C] and split it into its s1*s2 stride phases
    [B, s1*s2, hq, wq, C]: phase a*s2 + b holds padded[:, a::s1, b::s2].
    hq/wq cover every row/col a tap reads. For stride 1 this is the padded
    image with a unit phase axis."""
    s1, s2 = stride
    b, h, w, c = x.shape
    # lax.pad: a negative high pad crops rows/cols no tap reads. Only the
    # pad runs under the `phases` scope: XLA names instructions after the
    # op names of the reshapes and transpose, so a scope around them would
    # renumber instructions of the compiled program.
    with jax.named_scope("phases"):
        xp = jax.lax.pad(x, jnp.zeros((), x.dtype),
                         ((0, 0, 0), (pad_h[0], hq * s1 - h - pad_h[0], 0),
                          (pad_w[0], wq * s2 - w - pad_w[0], 0), (0, 0, 0)))
    xp = xp.reshape(b, hq, s1, wq, s2, c).transpose(0, 2, 4, 1, 3, 5)
    return xp.reshape(b, s1 * s2, hq, wq, c)


def _conv_pallas(x, w, *, f, gate_fn, gate_dt, gate_mode, crossbar_size,
                 stride, padding, block_h, block_n, interpret, scale2=None):
    """Run the fused conv (optionally emitting the gate) — returns
    (y [B, OH, OW, Cout] fp32, gate or None). The gate is
    [S, B, OH, OW, Cout/32] uint32 words when packed, else
    [S, B, OH, OW, Cout] gate_dt."""
    k1, k2, cin, cout = w.shape
    s1, s2 = stride
    (pt, pb), (pl_, pr) = _norm_padding(padding, (k1, k2), (1, 1))
    b, h, wd, _ = x.shape
    oh = (h + pt + pb - k1) // s1 + 1
    ow = (wd + pl_ + pr - k2) // s2 + 1

    bh = min(block_h, oh)
    # OH padded to a multiple of bh (the last block reads extra zero rows;
    # results sliced off)
    oh_pad = -(-oh // bh) * bh
    bn = min(block_n, cout)
    cout_pad = -(-cout // bn) * bn
    quantized = scale2 is not None
    # The work around the kernel runs under named scopes (`phases`,
    # `weights`, `crop`) that the benchmark's trace attribution reads; the
    # Pallas call keeps the jitted wrapper's name, which the roofline
    # readers match. Scopes are op-name metadata only.
    with jax.named_scope("phases"):
        if quantized:
            # int8 straight into the MXU; float primals of the STE path
            # hold the same integer codes
            x, w = x.astype(jnp.int8), w.astype(jnp.int8)
        else:
            dt = jnp.result_type(x.dtype, w.dtype)
            x, w = x.astype(dt), w.astype(dt)
    xph = _stride_phases(x, (pt, pb), (pl_, pr), stride,
                         oh_pad + (k1 - 1) // s1, ow + (k2 - 1) // s2)
    with jax.named_scope("weights"):
        w2d = w.reshape(k1 * k2 * cin, cout)
        if cout_pad != cout:
            w2d = jnp.pad(w2d, ((0, 0), (0, cout_pad - cout)))

    segs = _segment_taps(k1, k2, cin, crossbar_size)
    n_seg = len(segs)
    grid = (b, oh_pad // bh, cout_pad // bn)
    kw = dict(segs=segs, bh=bh, ow=ow, s1=s1, s2=s2, xbar=crossbar_size,
              bn=bn)
    with_gate = gate_mode in ("packed", "bytes")

    in_specs = [
        pl.BlockSpec((1,) + xph.shape[1:],
                     lambda bi, hi, ni: (bi, 0, 0, 0, 0)),
        pl.BlockSpec((k1 * k2 * cin, bn), lambda bi, hi, ni: (0, ni)),
    ]
    operands = [xph, w2d]
    if quantized:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        operands.append(scale2)
    out_specs = pl.BlockSpec(
        (1, bh, ow, bn), lambda bi, hi, ni: (bi, hi, 0, ni)
    )
    out_shape = jax.ShapeDtypeStruct((b, oh_pad, ow, cout_pad), jnp.float32)
    if with_gate:
        packed = gate_mode == "packed"
        gw = bn // GATE_PACK_WIDTH if packed else bn
        gn = cout_pad // GATE_PACK_WIDTH if packed else cout_pad
        gdt = jnp.uint32 if packed else gate_dt
        body = _q8_kernel_with_gate if quantized else _kernel_with_gate
        body = functools.partial(body, fn=f, gate_fn=gate_fn, packed=packed,
                                 **kw)
        out_specs = [
            out_specs,
            pl.BlockSpec((n_seg, 1, bh, ow, gw),
                         lambda bi, hi, ni: (0, bi, hi, 0, ni)),
        ]
        out_shape = [
            out_shape,
            jax.ShapeDtypeStruct((n_seg, b, oh_pad, ow, gn), gdt),
        ]
    else:
        body = _q8_kernel if quantized else _kernel
        body = functools.partial(body, fn=f, **kw)

    out = pl.pallas_call(
        body,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((bh * ow, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")
        ),
        interpret=interpret,
    )(*operands)
    with jax.named_scope("crop"):
        if with_gate:
            y, gate = out
            # Packed word columns cover the padded Cout and cannot be
            # cropped bit-wise (padded channels carry zero bits — zero w
            # columns).
            gate = (gate[:, :, :oh] if gate_mode == "packed"
                    else gate[:, :, :oh, :, :cout])
            return y[:, :oh, :, :cout], gate
        return out[:, :oh, :, :cout], None


@functools.lru_cache(maxsize=None)
def _diff_conv_op(crossbar_size: int, fn: str, stride: Tuple[int, int],
                  padding, block_h: int, block_n: int, interpret: bool,
                  save_gate: str = "auto"):
    f, gate_fn, gate_dt = _resolve_gate(fn)
    statics = dict(crossbar_size=crossbar_size, stride=stride,
                   padding=padding, block_h=block_h, block_n=block_n,
                   interpret=interpret)

    if gate_fn is None:
        return lambda x, w: _conv_pallas(x, w, f=f, gate_fn=None,
                                         gate_dt=None, gate_mode="none",
                                         **statics)[0]

    def _gate_mode(cout: int) -> str:
        # The kernel blocks Cout at bn = min(block_n, cout), so packability
        # is resolved against the EFFECTIVE bn: an explicit "packed"
        # request fails loudly (same contract as cadc_matmul_pallas) when
        # bn is not word-aligned; "auto" degrades to bytes.
        return _resolve_gate_mode(save_gate, fn, gate_dt,
                                  min(block_n, cout))

    @jax.custom_vjp
    def op(x, w):
        y, _ = _conv_pallas(x, w, f=f, gate_fn=gate_fn, gate_dt=gate_dt,
                            gate_mode="none", **statics)
        return y

    def op_fwd(x, w):
        y, gate = _conv_pallas(x, w, f=f, gate_fn=gate_fn, gate_dt=gate_dt,
                               gate_mode=_gate_mode(w.shape[3]), **statics)
        return y, (x, w, gate)

    def op_bwd(res, g):
        x, w, gate = res
        k1, k2, cin, cout = w.shape
        gate_mode = _gate_mode(cout)
        b, oh, ow_, _ = g.shape
        m = b * oh * ow_
        patches = im2col(x, (k1, k2), stride=stride, padding=padding)
        g2 = g.reshape(m, cout)
        gate2 = None if gate is None else gate.reshape(gate.shape[0], m, -1)
        dpat, dw2d = _segmented_bwd(
            g2, patches.reshape(m, k1 * k2 * cin),
            w.reshape(k1 * k2 * cin, cout), gate2,
            crossbar_size=crossbar_size, block_m=128, block_n=128,
            interpret=interpret,
            gate_fn=gate_fn if gate_mode == "recompute" else None,
            gate_packed=gate_mode == "packed",
        )
        dx = _col2im(dpat.reshape(b, oh, ow_, k1 * k2 * cin), x.shape,
                     (k1, k2), stride, padding)
        return dx.astype(x.dtype), dw2d.reshape(w.shape).astype(w.dtype)

    op.defvjp(op_fwd, op_bwd)
    return op


@functools.lru_cache(maxsize=None)
def _diff_conv_q8_op(crossbar_size: int, fn: str, stride: Tuple[int, int],
                     padding, block_h: int, block_n: int, interpret: bool,
                     save_gate: str = "auto"):
    """Straight-through custom_vjp over (x_q, w_codes, scale) — the conv
    analog of _diff_matmul_q8_op (int primals get float0, d(scale) =
    <dw_unscaled, w>)."""
    f, gate_fn, gate_dt = _resolve_gate(fn)
    statics = dict(crossbar_size=crossbar_size, stride=stride,
                   padding=padding, block_h=block_h, block_n=block_n,
                   interpret=interpret)

    def _run(x, w, scale, gate_mode):
        scale2 = scale.reshape(1, 1).astype(jnp.float32)
        return _conv_pallas(x, w, f=f, gate_fn=gate_fn, gate_dt=gate_dt,
                            gate_mode=gate_mode, scale2=scale2, **statics)

    if gate_fn is None:
        return lambda x, w, scale: _run(x, w, scale, "none")[0]

    def _gate_mode(cout: int) -> str:
        # Same effective-bn resolution as _diff_conv_op.
        return _resolve_gate_mode(save_gate, fn, gate_dt,
                                  min(block_n, cout))

    @jax.custom_vjp
    def op(x, w, scale):
        return _run(x, w, scale, "none")[0]

    def op_fwd(x, w, scale):
        y, gate = _run(x, w, scale, _gate_mode(w.shape[3]))
        return y, (x, w, scale, gate)

    def op_bwd(res, g):
        x, w, scale, gate = res
        s32 = scale.astype(jnp.float32).reshape(())
        k1, k2, cin, cout = w.shape
        gate_mode = _gate_mode(cout)
        b, oh, ow_, _ = g.shape
        m = b * oh * ow_
        patches = im2col(x, (k1, k2), stride=stride, padding=padding)
        g2 = g.reshape(m, cout)
        gate2 = None if gate is None else gate.reshape(gate.shape[0], m, -1)
        recompute = gate_mode == "recompute"
        dpat_u, dw2d_u = _segmented_bwd(
            g2, patches.reshape(m, k1 * k2 * cin),
            w.reshape(k1 * k2 * cin, cout), gate2,
            crossbar_size=crossbar_size, block_m=128, block_n=128,
            interpret=interpret,
            gate_fn=gate_fn if recompute else None,
            scale=s32 if recompute else None,
            gate_packed=gate_mode == "packed",
        )
        dscale = jnp.vdot(
            dw2d_u, w.reshape(k1 * k2 * cin, cout).astype(jnp.float32)
        ).astype(jnp.float32)
        dx = _col2im((s32 * dpat_u).reshape(b, oh, ow_, k1 * k2 * cin),
                     x.shape, (k1, k2), stride, padding)
        dw = (s32 * dw2d_u).reshape(w.shape)
        return (
            dx.astype(x.dtype) if jnp.issubdtype(x.dtype, jnp.floating)
            else _float0_zeros(x),
            dw.astype(w.dtype) if jnp.issubdtype(w.dtype, jnp.floating)
            else _float0_zeros(w),
            dscale.reshape(scale.shape).astype(scale.dtype),
        )

    op.defvjp(op_fwd, op_bwd)
    return op


@functools.partial(
    jax.jit,
    static_argnames=("crossbar_size", "fn", "stride", "padding", "block_h",
                     "block_n", "interpret", "save_gate"),
)
def _conv_jit(x, w, *, crossbar_size, fn, stride, padding, block_h, block_n,
              interpret, save_gate):
    op = _diff_conv_op(crossbar_size, fn, stride, padding, block_h,
                       block_n, interpret, save_gate)
    return op(x, w)


@functools.partial(
    jax.jit,
    static_argnames=("crossbar_size", "fn", "stride", "padding", "block_h",
                     "block_n", "interpret", "save_gate"),
)
def _conv_q8_jit(x_q, w_codes, scale, *, crossbar_size, fn, stride, padding,
                 block_h, block_n, interpret, save_gate):
    op = _diff_conv_q8_op(crossbar_size, fn, stride, padding, block_h,
                          block_n, interpret, save_gate)
    return op(x_q, w_codes, jnp.asarray(scale))


def _norm_call_args(stride, padding):
    # Hashability normalization must happen OUTSIDE the jit boundary —
    # list paddings/strides would otherwise die at jit dispatch.
    if not isinstance(padding, str):
        padding = tuple(tuple(p) for p in padding)
    return tuple(stride), padding


def _validate_save_gate(save_gate: str, fn: str, block_n: int, cout: int):
    """Eager save_gate validation (the VJP resolves lazily, under grad —
    an explicit 'packed' on an unpackable layout should fail on the
    FORWARD call, like cadc_matmul_pallas does)."""
    _, gate_fn, gate_dt = _resolve_gate(fn)
    if gate_fn is not None:
        _resolve_gate_mode(save_gate, fn, gate_dt, min(block_n, cout))


def cadc_conv2d_pallas(
    x: Array,
    w: Array,
    *,
    crossbar_size: int = 256,
    fn: str = "relu",
    stride: Tuple[int, int] = (1, 1),
    padding: Union[str, Sequence[Tuple[int, int]]] = "SAME",
    block_h: int = 8,
    block_n: int = 128,
    interpret: bool = False,
    save_gate: str = "auto",
) -> Array:
    """x [B,H,W,Cin] NHWC, w [K1,K2,Cin,Cout] HWIO -> [B,OH,OW,Cout] fp32.
    Differentiable via the custom_vjp; `save_gate` picks the gradient
    residual format (module docstring)."""
    stride, padding = _norm_call_args(stride, padding)
    _validate_save_gate(save_gate, fn, block_n, w.shape[3])
    return _conv_jit(x, w, crossbar_size=crossbar_size, fn=fn, stride=stride,
                     padding=padding, block_h=block_h, block_n=block_n,
                     interpret=interpret, save_gate=save_gate)


def cadc_conv2d_q8_pallas(
    x_q: Array,
    w_codes: Array,
    scale: Array,
    *,
    crossbar_size: int = 256,
    fn: str = "relu",
    stride: Tuple[int, int] = (1, 1),
    padding: Union[str, Sequence[Tuple[int, int]]] = "SAME",
    block_h: int = 8,
    block_n: int = 128,
    interpret: bool = False,
    save_gate: str = "auto",
) -> Array:
    """Quantized fused conv: x_q int8 [B,H,W,Cin], w_codes int8 {-1,0,1}
    [K1,K2,Cin,Cout], scale fp32 scalar (input_lsb * weight_alpha). Output
    fp32 [B,OH,OW,Cout] — bit-exact vs ref.cadc_conv2d_q8_ref. Gradients:
    straight-through for float primals, d(scale) always, float0 for int
    primals (module docstring)."""
    stride, padding = _norm_call_args(stride, padding)
    _validate_save_gate(save_gate, fn, block_n, w_codes.shape[3])
    return _conv_q8_jit(x_q, w_codes, scale, crossbar_size=crossbar_size,
                        fn=fn, stride=stride, padding=padding,
                        block_h=block_h, block_n=block_n,
                        interpret=interpret, save_gate=save_gate)


def _on_dendritic_register(_name: str) -> None:
    _diff_conv_op.cache_clear()
    _diff_conv_q8_op.cache_clear()
    _conv_jit.clear_cache()
    _conv_q8_jit.clear_cache()


dendritic.on_register(_on_dendritic_register)
