"""Tensor-parallel CADC linear via shard_map: the paper's psum locality as
an explicit collective schedule (beyond-paper optimization, EXPERIMENTS.md
§Perf).

Layout (DESIGN.md §5): the segment axis S of a CADC weight [S, xbar, N] is
sharded over the TP axis — a crossbar never spans devices, so the dendritic
f() is applied entirely device-locally and ONLY the (linear) cross-segment
sum crosses the wire. This file makes that schedule explicit:

    per device:  y_loc = sum_{s in local segments} f(x_s @ w_s)   (no comm)
    cross-dev:   y     = all_reduce(y_loc, axis)                  (1 AR)

and adds the TPU rebirth of the paper's psum zero-compression: the partial
outputs y_loc are cast to a narrow wire dtype (bf16) BEFORE the all-reduce,
halving TP collective bytes. The paper compresses psums on the macro's bus
because f() made them sparse/low-entropy; we compress the same quantity on
the ICI for the same reason (post-f() psum sums are activation-scaled and
tolerate bf16: see tests/test_tp_cadc.py error bounds).

vConv cannot do this locally-nonlinear trick at all: it must either move
RAW psums (S x the traffic) or sum before f() — CADC's math is what makes
the single compressed AR correct.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import dendritic

Array = jnp.ndarray


def segment_weights(w: Array, crossbar_size: int) -> Array:
    """[D, N] -> [S, xbar, N] (zero-padded D), the TP-shardable CADC layout."""
    d, n = w.shape
    s = -(-d // crossbar_size)
    pad = s * crossbar_size - d
    if pad:
        w = jnp.pad(w, ((0, pad), (0, 0)))
    return w.reshape(s, crossbar_size, n)


def tp_cadc_linear(
    x: Array,
    w_seg: Array,
    *,
    mesh: Mesh,
    axis: str = "model",
    fn: str = "relu",
    wire_dtype: Optional[jnp.dtype] = jnp.bfloat16,
) -> Array:
    """y[..., N] = sum_s f(x_s @ w_s), S sharded over mesh axis `axis`.

    x: [..., D] (replicated over `axis`; D = S * xbar).
    w_seg: [S, xbar, N] with S % axis_size == 0.
    wire_dtype: dtype of the partial outputs on the wire (None = fp32).
    """
    f = dendritic.get(fn)
    s, xbar, n = w_seg.shape
    t = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
    if s % t:
        raise ValueError(f"segments {s} not divisible by {axis} size {t}")

    def local(x_blk, w_blk):
        # x_blk [..., S_loc * xbar] (the segment shards of x), w_blk
        # [S_loc, xbar, N]: all segment psums + f() are device-local.
        s_loc = w_blk.shape[0]
        xs = x_blk.reshape(*x_blk.shape[:-1], s_loc, xbar)
        psums = jnp.einsum("...sk,skn->...sn", xs, w_blk,
                           preferred_element_type=jnp.float32)
        y_loc = jnp.sum(f(psums), axis=-2)
        if wire_dtype is not None:
            y_loc = y_loc.astype(wire_dtype)   # psum-compressed wire
        y = jax.lax.psum(y_loc, axis)          # the ONLY collective
        return y.astype(jnp.float32)

    nd = x.ndim - 1
    xspec = P(*([None] * nd), axis)  # D split along segments
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(xspec, P(axis, None, None)),
        out_specs=P(*([None] * (nd + 1))),
    )(x, w_seg)


def tp_vconv_linear(
    x: Array,
    w_seg: Array,
    *,
    mesh: Mesh,
    axis: str = "model",
) -> Array:
    """Baseline: identical layout, identity f — the exact TP matmul. The
    partial sums are raw (fp32 wire; bf16 would change the result beyond
    the quantization CADC already absorbed in f())."""
    return tp_cadc_linear(x, w_seg, mesh=mesh, axis=axis, fn="identity",
                          wire_dtype=None)
