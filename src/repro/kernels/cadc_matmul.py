"""Pallas TPU kernel: CADC segmented matmul with fused dendritic f().

TPU adaptation of the paper's crossbar pipeline (DESIGN.md §2): the
contraction dim D = S * xbar is blocked at the crossbar size INSIDE the
kernel body — the grid is (M/bm, N/bn), both parallel, and each kernel
instance loops its S segments over a VMEM scratch accumulator:

    acc = 0
    for s in range(S):                      # static, unrolled
        psum = x[:, s*xbar:(s+1)*xbar] @ w[s*xbar:(s+1)*xbar, :]   # MXU
        acc += f(psum)                      # IMA fused in VREG
    out[...] = acc                          # ONE output write per tile

Psums never touch HBM and — unlike the previous S-deep "arbitrary" grid
axis with an O(S) pl.when dispatch chain — the output tile is written
exactly once instead of being revisited S times, and the per-segment weight
slice is a proper k-loop the pipeliner can double-buffer. The VMEM working
set per step is bm*D + D*bn (inputs, x dtype) + bm*bn fp32 scratch: with
bm=bn=256, D=2048, bf16 inputs that is 1+1+0.25 MB, far under 16 MB.

Gradient residuals (save_gate)
------------------------------
Because f() is applied per segment BEFORE accumulation, the op is NOT a
plain matmul under autodiff: with p_s = x_s @ w_s and y = sum_s f(p_s),

    dx_s = (g ⊙ f'(p_s)) @ w_sᵀ      dw_s = x_sᵀ @ (g ⊙ f'(p_s))

where g is the output cotangent. Instead of saving O(M·S·N) fp32 psums, the
forward emits the per-segment gate f'(p_s) in one of three formats, chosen
by the `save_gate` knob (resolved per dendritic fn):

  * "packed"     — for indicator gates (dendritic.gate_packing, e.g. relu's
                   p_s > 0 bitmask): 32 gate bits lane-packed into one
                   uint32 word along N. Residual bytes S·M·N/8 — 8x less
                   HBM than the byte-bool, 32x less than fp32. Requires
                   block_n % 32 == 0.
  * "bytes"      — one element of dendritic.gate_dtype per gate (bool for
                   relu = S·M·N bytes, fp32 for curved fns = 4·S·M·N).
  * "recompute"  — NO residual (zero bytes): the backward kernels re-derive
                   the gate with one extra MXU matmul per block
                   (p_s = x_s @ w_s, gate = f'(p_s)) — flops-for-bytes, the
                   right trade when HBM, not MXU, is the bottleneck.
  * "auto"       — packed when the fn opts in and block_n allows, else
                   bytes. identity saves nothing in every mode.

Residual bytes per mode (M, N padded to block multiples, S = ceil(D/xbar)):

    packed    S*M*N/8        bytes     S*M*N*itemsize(gate_dtype)
    recompute 0              fp32 psums (never saved) would be 4*S*M*N

Both backward contractions run as Pallas kernels with an (parallel,
parallel, arbitrary) grid:

  * dx: grid (M/bm, S, N/bk), contracting over N, dx block [bm, xbar];
  * dw: grid (S, N/bn, M/bk), contracting over M, dw block [xbar, bn].

The packed backward unpacks the uint32 words in-VREG right before the
g ⊙ gate product; the recompute backward receives the x/w blocks it needs
anyway plus a (1,1) scale operand (1.0 for the float path) so the q8
variant recomputes gate = f'(scale * psum) exactly as the forward saw it.

The q8 path (int8 activations x ternary codes) gets a straight-through VJP:
grads are computed against the integer values as-if-fp32 (scaled by the
shared fp32 scale), cotangents for genuinely-int primals degrade to float0,
and d(scale) falls out for free as <dw_unscaled, w> (since dw_s/scale =
x_sᵀ(g ⊙ mask_s), summing dw ⊙ w over all segments telescopes to exactly
sum g ⊙ mask ⊙ psum_int). Int8-valued psums are < 2^24 so the fp32
recompute of the integer psum in the backward is exact.

Mosaic note: the pack/unpack reshape [m, n] <-> [m, n/32, 32] reduces over
the minor-most axis; whether that lowers to an efficient lane shuffle on
real TPU is part of the ROADMAP wall-clock validation pass (interpret-mode
correctness is CI-verified).
"""
from __future__ import annotations

import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import dendritic

Array = jnp.ndarray

# Gate bits per packed residual word (uint32 lane packing along N).
GATE_PACK_WIDTH = 32

SAVE_GATE_MODES = ("auto", "packed", "bytes", "recompute")

# Forward VMEM working-set budget: above this the forward re-blocks D
# over an "arbitrary" grid axis (half of the ~16 MB/core VMEM, leaving
# headroom for the pipeliner's double buffering).
FWD_VMEM_BUDGET = 8 * 2**20


def _pack_mask(gate: Array) -> Array:
    """[m, n] indicator gate -> [m, n/32] uint32, bit b of word w = gate
    column 32*w + b (n % 32 == 0). Nonzero gate values map to set bits."""
    m, n = gate.shape
    nw = n // GATE_PACK_WIDTH
    bits = (gate != 0).astype(jnp.uint32).reshape(m, nw, GATE_PACK_WIDTH)
    shifts = jax.lax.broadcasted_iota(jnp.uint32, (m, nw, GATE_PACK_WIDTH), 2)
    # bits are disjoint per lane, so a dtype-pinned sum IS the bitwise or.
    return jnp.sum(bits << shifts, axis=2, dtype=jnp.uint32)


def _unpack_mask(words: Array) -> Array:
    """[m, nw] uint32 -> [m, nw*32] fp32 {0,1} gate (inverse of _pack_mask)."""
    m, nw = words.shape
    shifts = jax.lax.broadcasted_iota(jnp.uint32, (m, nw, GATE_PACK_WIDTH), 2)
    bits = (words[:, :, None] >> shifts) & jnp.uint32(1)
    return bits.reshape(m, nw * GATE_PACK_WIDTH).astype(jnp.float32)


# ---------------------------------------------------------------------------
# Forward kernels: grid (M/bm, N/bn), segments looped in-body over a VMEM
# scratch accumulator — one output write per tile.
#
# VMEM ceiling (ROADMAP): the 2-D grid holds full [bm, D] / [D, bn] strips
# resident, which approaches the 16 MB budget at LM scale (D >~ 16k with
# bm = bn = 256 fp32). When the estimated working set exceeds
# `vmem_budget_bytes`, the forward re-blocks D at k*xbar granularity: the
# grid grows an "arbitrary" third axis over D-chunks, each chunk keeps the
# in-kernel segment loop over its own k segments, and the scratch
# accumulator carries the partial sum across chunks (output still written
# once, after the last chunk). Segment accumulation ORDER is preserved —
# each segment still adds into the accumulator individually — so the
# chunked forward is bit-identical to the unchunked one (and the q8 path
# stays bit-exact vs the sequential oracle). The gate residual layout
# ([S, M, N']) is unchanged: chunk c writes gate rows [c*k, (c+1)*k), so
# the backward kernels never know chunking happened.
# ---------------------------------------------------------------------------

def _seg_psum(x_ref, w_ref, s: int, xbar: int) -> Array:
    return jnp.dot(
        x_ref[:, s * xbar:(s + 1) * xbar],
        w_ref[s * xbar:(s + 1) * xbar, :],
        preferred_element_type=jnp.float32,
    )


def _seg_psum_q8(x_ref, w_ref, scale_ref, s: int, xbar: int) -> Array:
    psum_i32 = jnp.dot(
        x_ref[:, s * xbar:(s + 1) * xbar],
        w_ref[s * xbar:(s + 1) * xbar, :],
        preferred_element_type=jnp.int32,
    )
    return psum_i32.astype(jnp.float32) * scale_ref[0, 0]


def _acc_first(acc_ref, fps, chunked: bool):
    """Segment 0 of a grid step: (re)initialize the accumulator on the
    first D-chunk, add on later chunks. Unchunked grids have no chunk axis
    — segment 0 always initializes."""
    if not chunked:
        acc_ref[...] = fps
        return
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        acc_ref[...] = fps

    @pl.when(c > 0)
    def _add():
        acc_ref[...] += fps


def _flush(o_ref, acc_ref, chunked: bool):
    """One output write per tile — after the last D-chunk when chunked."""
    if not chunked:
        o_ref[...] = acc_ref[...]
        return

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _write():
        o_ref[...] = acc_ref[...]


def _kernel(x_ref, w_ref, o_ref, acc_ref, *, fn: Callable, n_seg: int,
            xbar: int, chunked: bool = False):
    for s in range(n_seg):
        fps = fn(_seg_psum(x_ref, w_ref, s, xbar))
        if s == 0:
            _acc_first(acc_ref, fps, chunked)
        else:
            acc_ref[...] += fps
    _flush(o_ref, acc_ref, chunked)


def _kernel_with_gate(x_ref, w_ref, o_ref, g_ref, acc_ref, *, fn: Callable,
                      gate_fn: Callable, n_seg: int, xbar: int, packed: bool,
                      chunked: bool = False):
    """VJP forward: also writes each segment's gate f'(psum) while the psum
    tile is still in VREGs — packed to uint32 words when `packed`. The
    gate block of a D-chunk covers exactly its own segments, so chunking
    leaves the [S, M, N'] residual layout untouched."""
    for s in range(n_seg):
        psum = _seg_psum(x_ref, w_ref, s, xbar)
        gate = gate_fn(psum)
        g_ref[s] = _pack_mask(gate) if packed else gate.astype(g_ref.dtype)
        fps = fn(psum)
        if s == 0:
            _acc_first(acc_ref, fps, chunked)
        else:
            acc_ref[...] += fps
    _flush(o_ref, acc_ref, chunked)


def _q8_kernel(x_ref, w_ref, scale_ref, o_ref, acc_ref, *, fn: Callable,
               n_seg: int, xbar: int, chunked: bool = False):
    """Quantized variant: int8 activations x int8 ternary codes -> int32
    psums on the MXU, rescaled to fp32 before f(). scale_ref is (1,1)
    fp32 = (input_scale * weight_alpha)."""
    for s in range(n_seg):
        fps = fn(_seg_psum_q8(x_ref, w_ref, scale_ref, s, xbar))
        if s == 0:
            _acc_first(acc_ref, fps, chunked)
        else:
            acc_ref[...] += fps
    _flush(o_ref, acc_ref, chunked)


def _q8_kernel_with_gate(x_ref, w_ref, scale_ref, o_ref, g_ref, acc_ref, *,
                         fn: Callable, gate_fn: Callable, n_seg: int,
                         xbar: int, packed: bool, chunked: bool = False):
    for s in range(n_seg):
        psum = _seg_psum_q8(x_ref, w_ref, scale_ref, s, xbar)
        gate = gate_fn(psum)
        g_ref[s] = _pack_mask(gate) if packed else gate.astype(g_ref.dtype)
        fps = fn(psum)
        if s == 0:
            _acc_first(acc_ref, fps, chunked)
        else:
            acc_ref[...] += fps
    _flush(o_ref, acc_ref, chunked)


# ---------------------------------------------------------------------------
# Backward Pallas kernels: the two segmented MXU contractions of the VJP.
# ---------------------------------------------------------------------------

def _bwd_dx_kernel(g_ref, m_ref, w_ref, o_ref, *, packed: bool):
    """dx block [bm, xbar] for segment s = sum_k (g ⊙ mask)[bm,bk] @ w[xbar,bk]ᵀ."""
    k = pl.program_id(2)
    mask = _unpack_mask(m_ref[0]) if packed else m_ref[0].astype(jnp.float32)
    gm = g_ref[...] * mask
    part = jax.lax.dot_general(
        gm, w_ref[...].astype(jnp.float32),
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == 0)
    def _init():
        o_ref[...] = part

    @pl.when(k > 0)
    def _acc():
        o_ref[...] += part


def _bwd_dx_kernel_nomask(g_ref, w_ref, o_ref):
    k = pl.program_id(2)
    part = jax.lax.dot_general(
        g_ref[...], w_ref[...].astype(jnp.float32),
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == 0)
    def _init():
        o_ref[...] = part

    @pl.when(k > 0)
    def _acc():
        o_ref[...] += part


def _bwd_dx_kernel_recompute(g_ref, x_ref, w_ref, scale_ref, o_ref, *,
                             gate_fn: Callable):
    """save_gate='recompute': re-derive the gate from the segment psum
    (one extra MXU matmul) instead of reading a residual from HBM."""
    k = pl.program_id(2)
    wf = w_ref[...].astype(jnp.float32)
    psum = jnp.dot(x_ref[...].astype(jnp.float32), wf,
                   preferred_element_type=jnp.float32) * scale_ref[0, 0]
    gm = g_ref[...] * gate_fn(psum).astype(jnp.float32)
    part = jax.lax.dot_general(
        gm, wf,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == 0)
    def _init():
        o_ref[...] = part

    @pl.when(k > 0)
    def _acc():
        o_ref[...] += part


def _bwd_dw_kernel(x_ref, g_ref, m_ref, o_ref, *, packed: bool):
    """dw block [xbar, bn] for segment s = sum_k x[bk,xbar]ᵀ @ (g ⊙ mask)[bk,bn]."""
    k = pl.program_id(2)
    mask = _unpack_mask(m_ref[0]) if packed else m_ref[0].astype(jnp.float32)
    gm = g_ref[...] * mask
    part = jax.lax.dot_general(
        x_ref[...].astype(jnp.float32), gm,
        dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == 0)
    def _init():
        o_ref[...] = part

    @pl.when(k > 0)
    def _acc():
        o_ref[...] += part


def _bwd_dw_kernel_nomask(x_ref, g_ref, o_ref):
    k = pl.program_id(2)
    part = jax.lax.dot_general(
        x_ref[...].astype(jnp.float32), g_ref[...],
        dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == 0)
    def _init():
        o_ref[...] = part

    @pl.when(k > 0)
    def _acc():
        o_ref[...] += part


def _bwd_dw_kernel_recompute(x_ref, g_ref, w_ref, scale_ref, o_ref, *,
                             gate_fn: Callable):
    k = pl.program_id(2)
    xf = x_ref[...].astype(jnp.float32)
    psum = jnp.dot(xf, w_ref[...].astype(jnp.float32),
                   preferred_element_type=jnp.float32) * scale_ref[0, 0]
    gm = g_ref[...] * gate_fn(psum).astype(jnp.float32)
    part = jax.lax.dot_general(
        xf, gm,
        dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == 0)
    def _init():
        o_ref[...] = part

    @pl.when(k > 0)
    def _acc():
        o_ref[...] += part


def _pad_to(x: Array, axis: int, mult: int) -> Array:
    d = x.shape[axis]
    pad = (-d) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _fit_axis(x: Array, axis: int, size: int) -> Array:
    """Zero-pad or slice `axis` to exactly `size` elements."""
    d = x.shape[axis]
    if d < size:
        widths = [(0, 0)] * x.ndim
        widths[axis] = (0, size - d)
        return jnp.pad(x, widths)
    if d > size:
        return jax.lax.slice_in_dim(x, 0, size, axis=axis)
    return x


def _dim_sem(n: int = 3):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",) * (n - 1) + ("arbitrary",))


def _auto_d_chunk(dp: int, bm: int, bn: int, itemsize: int, xbar: int,
                  gate_bytes_per_seg: int, budget: int) -> Optional[int]:
    """D-chunk width (a multiple of xbar dividing dp) for the forward, or
    None to keep the whole-D strips resident. The working-set estimate per
    grid step is the two input strips + the fp32 accumulator + the chunk's
    gate-residual block."""
    n_seg = dp // xbar
    acc = bm * bn * 4

    def fits(k: int) -> bool:
        return ((bm + bn) * k * xbar * itemsize
                + k * gate_bytes_per_seg + acc) <= budget

    if fits(n_seg):
        return None
    best = 1  # k = 1 (one crossbar per chunk) is the floor
    for k in range(2, n_seg):
        if n_seg % k == 0 and fits(k):
            best = k
    return best * xbar


def _fwd_pallas(xp, wp, *, f, gate_fn, gate_mode, gate_dt, xbar, bm, bn,
                interpret, scale2=None, d_chunk=None):
    """Run the forward on pre-padded operands. gate_mode 'packed'/'bytes'
    adds the gate residual output; anything else runs residual-free.
    d_chunk re-blocks D over an "arbitrary" grid axis (module note above);
    None keeps the whole-D 2-D grid."""
    mp, dp = xp.shape
    np_ = wp.shape[1]
    chunked = d_chunk is not None and d_chunk < dp
    dc = d_chunk if chunked else dp
    n_seg = dc // xbar                     # segments per grid step
    grid = (mp // bm, np_ // bn) + ((dp // dc,) if chunked else ())
    with_gate = gate_mode in ("packed", "bytes")
    quantized = scale2 is not None

    if chunked:
        in_specs = [
            pl.BlockSpec((bm, dc), lambda i, j, c: (i, c)),
            pl.BlockSpec((dc, bn), lambda i, j, c: (c, j)),
        ]
    else:
        in_specs = [
            pl.BlockSpec((bm, dp), lambda i, j: (i, 0)),
            pl.BlockSpec((dp, bn), lambda i, j: (0, j)),
        ]
    operands = [xp, wp]
    if quantized:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        operands.append(scale2)

    out_specs = pl.BlockSpec(
        (bm, bn), (lambda i, j, c: (i, j)) if chunked
        else (lambda i, j: (i, j)))
    out_shape = jax.ShapeDtypeStruct((mp, np_), jnp.float32)
    kw = dict(fn=f, n_seg=n_seg, xbar=xbar, chunked=chunked)
    if with_gate:
        packed = gate_mode == "packed"
        gw = bn // GATE_PACK_WIDTH if packed else bn
        gn = np_ // GATE_PACK_WIDTH if packed else np_
        gdt = jnp.uint32 if packed else gate_dt
        out_specs = [
            out_specs,
            pl.BlockSpec((n_seg, bm, gw),
                         (lambda i, j, c: (c, i, j)) if chunked
                         else (lambda i, j: (0, i, j))),
        ]
        out_shape = [
            out_shape,
            jax.ShapeDtypeStruct((dp // xbar, mp, gn), gdt),
        ]
        body = _q8_kernel_with_gate if quantized else _kernel_with_gate
        body = functools.partial(body, gate_fn=gate_fn, packed=packed, **kw)
    else:
        body = _q8_kernel if quantized else _kernel
        body = functools.partial(body, **kw)

    return pl.pallas_call(
        body,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
            [: len(grid)]
        ),
        interpret=interpret,
    )(*operands)


def _segmented_bwd(
    g: Array,
    x2: Array,
    w: Array,
    gate: Optional[Array],
    *,
    crossbar_size: int,
    block_m: int,
    block_n: int,
    interpret: bool,
    gate_fn: Optional[Callable] = None,
    scale: Optional[Array] = None,
    gate_packed: bool = False,
) -> Tuple[Array, Array]:
    """The shared VJP contraction pair on UNPADDED 2-D operands.

    g [m, n] output cotangent, x2 [m, d], w [d, n]. The gate residual
    selects the mode:

      * gate + gate_packed=True  — [S, m', nw] uint32 bitmask words,
        unpacked in-VREG (the caller states the format explicitly: a
        custom fn may legitimately store non-packed uint32 gate VALUES);
      * gate + gate_packed=False — [S, m', n'] one gate element per psum;
      * gate None, gate_fn set   — recompute: gate re-derived from
        f'(scale * x_s @ w_s) inside the backward kernels (scale defaults
        to 1; the q8 path passes input_scale * alpha);
      * gate None, gate_fn None  — identity (no mask applied).

    Returns (dx [m, d], dw [d, n]) in fp32. Also reused by the conv VJP
    with x2 = im2col patches.
    """
    m, d = x2.shape
    n = w.shape[1]
    xp = _pad_to(_pad_to(x2, 1, crossbar_size), 0, block_m)
    wp = _pad_to(_pad_to(w, 0, crossbar_size), 1, block_n)
    gp = _pad_to(_pad_to(g.astype(jnp.float32), 1, block_n), 0, block_m)
    mp, dp = xp.shape
    np_ = wp.shape[1]
    n_seg = dp // crossbar_size

    packed = gate is not None and gate_packed
    recompute = gate is None and gate_fn is not None
    if packed and block_n % GATE_PACK_WIDTH != 0:
        raise ValueError(
            f"packed gate backward needs block_n % {GATE_PACK_WIDTH} == 0, "
            f"got {block_n}"
        )

    if recompute:
        scale2 = (jnp.ones((1, 1), jnp.float32) if scale is None
                  else jnp.asarray(scale, jnp.float32).reshape(1, 1))
        dx_body = functools.partial(_bwd_dx_kernel_recompute, gate_fn=gate_fn)
        dw_body = functools.partial(_bwd_dw_kernel_recompute, gate_fn=gate_fn)
        dx_specs = [
            pl.BlockSpec((block_m, block_n), lambda i, s, k: (i, k)),
            pl.BlockSpec((block_m, crossbar_size), lambda i, s, k: (i, s)),
            pl.BlockSpec((crossbar_size, block_n), lambda i, s, k: (s, k)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ]
        dw_specs = [
            pl.BlockSpec((block_m, crossbar_size), lambda s, j, k: (k, s)),
            pl.BlockSpec((block_m, block_n), lambda s, j, k: (k, j)),
            pl.BlockSpec((crossbar_size, block_n), lambda s, j, k: (s, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ]
        args_dx = [gp, xp, wp, scale2]
        args_dw = [xp, gp, wp, scale2]
    elif gate is not None:
        gw = block_n // GATE_PACK_WIDTH if packed else block_n
        gn = np_ // GATE_PACK_WIDTH if packed else np_
        # The forward may have padded N at a different block granularity
        # (the conv VJP re-blocks at 128): fit rows to mp, words/cols to gn.
        gatep = _fit_axis(_fit_axis(gate, 1, mp), 2, gn)
        dx_body = functools.partial(_bwd_dx_kernel, packed=packed)
        dw_body = functools.partial(_bwd_dw_kernel, packed=packed)
        dx_specs = [
            pl.BlockSpec((block_m, block_n), lambda i, s, k: (i, k)),
            pl.BlockSpec((1, block_m, gw), lambda i, s, k: (s, i, k)),
            pl.BlockSpec((crossbar_size, block_n), lambda i, s, k: (s, k)),
        ]
        dw_specs = [
            pl.BlockSpec((block_m, crossbar_size), lambda s, j, k: (k, s)),
            pl.BlockSpec((block_m, block_n), lambda s, j, k: (k, j)),
            pl.BlockSpec((1, block_m, gw), lambda s, j, k: (s, k, j)),
        ]
        args_dx = [gp, gatep, wp]
        args_dw = [xp, gp, gatep]
    else:
        dx_body, dw_body = _bwd_dx_kernel_nomask, _bwd_dw_kernel_nomask
        dx_specs = [
            pl.BlockSpec((block_m, block_n), lambda i, s, k: (i, k)),
            pl.BlockSpec((crossbar_size, block_n), lambda i, s, k: (s, k)),
        ]
        dw_specs = [
            pl.BlockSpec((block_m, crossbar_size), lambda s, j, k: (k, s)),
            pl.BlockSpec((block_m, block_n), lambda s, j, k: (k, j)),
        ]
        args_dx = [gp, wp]
        args_dw = [xp, gp]

    dx = pl.pallas_call(
        dx_body,
        grid=(mp // block_m, n_seg, np_ // block_n),
        in_specs=dx_specs,
        out_specs=pl.BlockSpec((block_m, crossbar_size), lambda i, s, k: (i, s)),
        out_shape=jax.ShapeDtypeStruct((mp, dp), jnp.float32),
        compiler_params=_dim_sem(),
        interpret=interpret,
    )(*args_dx)
    dw = pl.pallas_call(
        dw_body,
        grid=(n_seg, np_ // block_n, mp // block_m),
        in_specs=dw_specs,
        out_specs=pl.BlockSpec((crossbar_size, block_n), lambda s, j, k: (s, j)),
        out_shape=jax.ShapeDtypeStruct((dp, np_), jnp.float32),
        compiler_params=_dim_sem(),
        interpret=interpret,
    )(*args_dw)
    return dx[:m, :d], dw[:d, :n]


def _float0_zeros(x: Array):
    return np.zeros(x.shape, dtype=jax.dtypes.float0)


def _resolve_gate(fn: str):
    """(f, gate_fn, gate_dtype) for a registered fn; gate_fn None when the
    fn has no derivative (the op factories then skip the VJP — forward-only,
    matching the XLA-only-training contract of dendritic.register)."""
    f = dendritic.get(fn)
    try:
        return f, dendritic.grad(fn), dendritic.gate_dtype(fn)
    except ValueError:
        return f, None, None


def _resolve_gate_mode(save_gate: str, fn: str, gate_dt, block_n: int) -> str:
    """Resolve the user-facing save_gate knob to a concrete residual mode:
    'none' | 'packed' | 'bytes' | 'recompute' (module docstring)."""
    if save_gate not in SAVE_GATE_MODES:
        raise ValueError(
            f"save_gate={save_gate!r}; choose from {SAVE_GATE_MODES}"
        )
    if gate_dt is None:
        return "none"  # identity-like: f' ≡ 1, nothing to save or recompute
    if save_gate == "recompute":
        return "recompute"
    packable = dendritic.gate_packing(fn) and block_n % GATE_PACK_WIDTH == 0
    if save_gate == "packed":
        if not packable:
            raise ValueError(
                f"save_gate='packed' needs an indicator gate "
                f"(dendritic.gate_packing({fn!r}) is "
                f"{dendritic.gate_packing(fn)}) and block_n % "
                f"{GATE_PACK_WIDTH} == 0 (got {block_n})"
            )
        return "packed"
    if save_gate == "bytes":
        return "bytes"
    return "packed" if packable else "bytes"


def gate_residual_nbytes(
    m: int,
    d: int,
    n: int,
    *,
    crossbar_size: int,
    fn: str,
    block_m: int = 256,
    block_n: int = 256,
    save_gate: str = "auto",
) -> int:
    """Analytic HBM bytes of the gate residual the VJP forward saves for an
    [m, d] @ [d, n] CADC matmul — the quantity kernel_bench budgets."""
    _, gate_fn, gate_dt = _resolve_gate(fn)
    if gate_fn is None:
        return 0
    mode = _resolve_gate_mode(save_gate, fn, gate_dt, block_n)
    if mode in ("none", "recompute"):
        return 0
    mp = -(-m // block_m) * block_m
    np_ = -(-n // block_n) * block_n
    s = -(-d // crossbar_size)
    if mode == "packed":
        return s * mp * (np_ // GATE_PACK_WIDTH) * 4
    return s * mp * np_ * jnp.dtype(gate_dt).itemsize


def cadc_matmul_fwd_residuals(
    x2: Array,
    w: Array,
    *,
    crossbar_size: int,
    fn: str,
    block_m: int = 256,
    block_n: int = 256,
    interpret: bool = True,
    save_gate: str = "auto",
) -> Tuple[Array, Optional[Array]]:
    """Bench/debug entry: run the VJP forward and return (y, gate residual
    or None) so the residual's actual size/dtype can be inspected."""
    f, gate_fn, gate_dt = _resolve_gate(fn)
    mode = ("none" if gate_fn is None
            else _resolve_gate_mode(save_gate, fn, gate_dt, block_n))
    m, d = x2.shape
    n = w.shape[1]
    xp = _pad_to(_pad_to(x2, 1, crossbar_size), 0, block_m)
    wp = _pad_to(_pad_to(w, 0, crossbar_size), 1, block_n)
    out = _fwd_pallas(
        xp, wp, f=f, gate_fn=gate_fn, gate_mode=mode, gate_dt=gate_dt,
        xbar=crossbar_size, bm=block_m, bn=block_n, interpret=interpret,
    )
    if mode in ("packed", "bytes"):
        y, gate = out
        return y[:m, :n], gate
    return out[:m, :n], None


def _gate_block_bytes(gate_mode: str, gate_dt, bm: int, bn: int) -> int:
    if gate_mode == "packed":
        return bm * (bn // GATE_PACK_WIDTH) * 4
    if gate_mode == "bytes":
        return bm * bn * jnp.dtype(gate_dt).itemsize
    return 0


@functools.lru_cache(maxsize=None)
def _diff_matmul_op(crossbar_size: int, fn: str, block_m: int, block_n: int,
                    interpret: bool, save_gate: str = "auto",
                    vmem_budget_bytes: int = FWD_VMEM_BUDGET):
    """custom_vjp op over unpadded 2-D (x, w), statics baked in (cached so
    repeated traces under jit reuse one op identity). A fn registered
    without a derivative still runs forward-only (no VJP attached)."""
    f, gate_fn, gate_dt = _resolve_gate(fn)

    def _run(x2, w, gate_mode):
        m, d = x2.shape
        n = w.shape[1]
        xp = _pad_to(_pad_to(x2, 1, crossbar_size), 0, block_m)
        wp = _pad_to(_pad_to(w, 0, crossbar_size), 1, block_n)
        d_chunk = _auto_d_chunk(
            xp.shape[1], block_m, block_n,
            max(jnp.dtype(x2.dtype).itemsize, jnp.dtype(w.dtype).itemsize),
            crossbar_size,
            _gate_block_bytes(gate_mode, gate_dt, block_m, block_n),
            vmem_budget_bytes,
        )
        out = _fwd_pallas(
            xp, wp, f=f, gate_fn=gate_fn, gate_mode=gate_mode,
            gate_dt=gate_dt, xbar=crossbar_size, bm=block_m, bn=block_n,
            interpret=interpret, d_chunk=d_chunk,
        )
        if gate_mode in ("packed", "bytes"):
            y, gate = out
            # Packed word columns cover the padded N and cannot be cropped
            # bit-wise; padded columns carry zero bits (zero w columns).
            gate = gate[:, :m, :] if gate_mode == "packed" else gate[:, :m, :n]
            return y[:m, :n], gate
        return out[:m, :n], None

    if gate_fn is None:
        return lambda x2, w: _run(x2, w, "none")[0]

    gate_mode = _resolve_gate_mode(save_gate, fn, gate_dt, block_n)

    @jax.custom_vjp
    def op(x2, w):
        return _run(x2, w, "none")[0]

    def op_fwd(x2, w):
        y, gate = _run(x2, w, gate_mode)
        return y, (x2, w, gate)

    def op_bwd(res, g):
        x2, w, gate = res
        dx, dw = _segmented_bwd(
            g, x2, w, gate, crossbar_size=crossbar_size,
            block_m=block_m, block_n=block_n, interpret=interpret,
            gate_fn=gate_fn if gate_mode == "recompute" else None,
            gate_packed=gate_mode == "packed",
        )
        return dx.astype(x2.dtype), dw.astype(w.dtype)

    op.defvjp(op_fwd, op_bwd)
    return op


@functools.lru_cache(maxsize=None)
def _diff_matmul_q8_op(crossbar_size: int, fn: str, block_m: int, block_n: int,
                       interpret: bool, save_gate: str = "auto",
                       vmem_budget_bytes: int = FWD_VMEM_BUDGET):
    """Straight-through custom_vjp over (x_q, w_codes, scale).

    Cotangents for the integer codes are computed as-if-fp32 (STE) and only
    materialize when the primal is a float array (e.g. fake-quant training);
    genuinely-int primals receive float0. d(scale) = <dw/scale, w> — see
    module docstring. A fn without a registered derivative runs
    forward-only (no VJP attached).
    """
    f, gate_fn, gate_dt = _resolve_gate(fn)

    def _run(x2, w, scale, gate_mode):
        m, d = x2.shape
        n = w.shape[1]
        # int8 straight into the MXU; float primals of the STE path hold
        # the same integer codes
        xp = _pad_to(_pad_to(x2.astype(jnp.int8), 1, crossbar_size), 0,
                     block_m)
        wp = _pad_to(_pad_to(w.astype(jnp.int8), 0, crossbar_size), 1,
                     block_n)
        scale2 = scale.reshape(1, 1).astype(jnp.float32)
        d_chunk = _auto_d_chunk(
            xp.shape[1], block_m, block_n,
            1,  # int8 strips
            crossbar_size,
            _gate_block_bytes(gate_mode, gate_dt, block_m, block_n),
            vmem_budget_bytes,
        )
        out = _fwd_pallas(
            xp, wp, f=f, gate_fn=gate_fn, gate_mode=gate_mode,
            gate_dt=gate_dt, xbar=crossbar_size, bm=block_m, bn=block_n,
            interpret=interpret, scale2=scale2, d_chunk=d_chunk,
        )
        if gate_mode in ("packed", "bytes"):
            y, gate = out
            gate = gate[:, :m, :] if gate_mode == "packed" else gate[:, :m, :n]
            return y[:m, :n], gate
        return out[:m, :n], None

    if gate_fn is None:
        return lambda x2, w, scale: _run(x2, w, scale, "none")[0]

    gate_mode = _resolve_gate_mode(save_gate, fn, gate_dt, block_n)

    @jax.custom_vjp
    def op(x2, w, scale):
        return _run(x2, w, scale, "none")[0]

    def op_fwd(x2, w, scale):
        y, gate = _run(x2, w, scale, gate_mode)
        return y, (x2, w, scale, gate)

    def op_bwd(res, g):
        x2, w, scale, gate = res
        s32 = scale.astype(jnp.float32).reshape(())
        dxu, dwu = _segmented_bwd(
            g, x2, w, gate, crossbar_size=crossbar_size,
            block_m=block_m, block_n=block_n, interpret=interpret,
            gate_fn=gate_fn if gate_mode == "recompute" else None,
            scale=s32 if gate_mode == "recompute" else None,
            gate_packed=gate_mode == "packed",
        )
        # y = sum_s f(scale * p_s): chain rule adds one scale factor to
        # dx/dw, and d(scale) telescopes to <dw_unscaled, w>.
        dscale = jnp.vdot(dwu, w.astype(jnp.float32)).astype(jnp.float32)
        dx = (s32 * dxu)
        dw = (s32 * dwu)
        return (
            dx.astype(x2.dtype) if jnp.issubdtype(x2.dtype, jnp.floating)
            else _float0_zeros(x2),
            dw.astype(w.dtype) if jnp.issubdtype(w.dtype, jnp.floating)
            else _float0_zeros(w),
            dscale.reshape(scale.shape).astype(scale.dtype),
        )

    op.defvjp(op_fwd, op_bwd)
    return op


@functools.partial(
    jax.jit,
    static_argnames=("crossbar_size", "fn", "block_m", "block_n", "interpret",
                     "save_gate", "vmem_budget_bytes"),
)
def cadc_matmul_pallas(
    x: Array,
    w: Array,
    *,
    crossbar_size: int = 256,
    fn: str = "relu",
    block_m: int = 256,
    block_n: int = 256,
    interpret: bool = False,
    save_gate: str = "auto",
    vmem_budget_bytes: int = FWD_VMEM_BUDGET,
) -> Array:
    """y[M,N] = sum_s f( x[:, s*xbar:(s+1)*xbar] @ w[s*xbar:(s+1)*xbar, :] ).

    x: [M, D] (or [..., D], flattened internally), w: [D, N]. Output fp32.
    Differentiable: jax.grad flows through the custom_vjp whose backward is
    itself two segmented Pallas kernels; `save_gate` picks the gradient
    residual format — packed uint32 bitmask / byte gate / recompute-in-
    backward (module docstring). When the forward's resident strips would
    exceed `vmem_budget_bytes`, D is auto-re-blocked at k*xbar granularity
    over an "arbitrary" grid axis — bit-identical output (segment
    accumulation order preserved), bounded VMEM.
    """
    *lead, d = x.shape
    n = w.shape[1]
    if w.shape[0] != d:
        raise ValueError(f"contraction mismatch {x.shape} @ {w.shape}")
    op = _diff_matmul_op(crossbar_size, fn, block_m, block_n, interpret,
                         save_gate, vmem_budget_bytes)
    y = op(x.reshape(-1, d), w)
    return y.reshape(*lead, n)


@functools.partial(
    jax.jit,
    static_argnames=("crossbar_size", "fn", "block_m", "block_n", "interpret",
                     "save_gate", "vmem_budget_bytes"),
)
def cadc_matmul_q8_pallas(
    x_q: Array,
    w_codes: Array,
    scale: Array,
    *,
    crossbar_size: int = 256,
    fn: str = "relu",
    block_m: int = 256,
    block_n: int = 256,
    interpret: bool = False,
    save_gate: str = "auto",
    vmem_budget_bytes: int = FWD_VMEM_BUDGET,
) -> Array:
    """Quantized CADC: x_q int8 [M, D], w_codes int8 {-1,0,1} [D, N],
    scale fp32 scalar (input_lsb * weight_alpha). Output fp32.
    Differentiable wrt scale always, and wrt x_q/w_codes straight-through
    when they are float arrays (QAT); int primals get float0 cotangents."""
    *lead, d = x_q.shape
    n = w_codes.shape[1]
    op = _diff_matmul_q8_op(crossbar_size, fn, block_m, block_n, interpret,
                            save_gate, vmem_budget_bytes)
    y = op(x_q.reshape(-1, d), w_codes, jnp.asarray(scale))
    return y.reshape(*lead, n)


def _on_dendritic_register(_name: str) -> None:
    """Drop compiled ops when a dendritic fn is (re-)registered — both the
    op factories and the jit wrappers cache on the fn NAME, which would
    otherwise keep serving the old callable."""
    _diff_matmul_op.cache_clear()
    _diff_matmul_q8_op.cache_clear()
    cadc_matmul_pallas.clear_cache()
    cadc_matmul_q8_pallas.clear_cache()


dendritic.on_register(_on_dendritic_register)
