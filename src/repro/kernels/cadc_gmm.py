"""Pallas TPU kernel: grouped CADC matmul over the experts a chip holds.

    y[r] = sum_s f( x[r, s*xbar:(s+1)*xbar] @ w[e(r)][s*xbar:(s+1)*xbar, :] )

`x` holds the rows routed to this chip's experts, sorted by expert, and
each expert's group starts on a row-tile boundary (`tile_layout`): every
tile of `block_m` rows then belongs to one expert. A scalar-prefetched
tile -> expert map picks that expert's weight block in the index map, so
the grid (tiles, N / block_n) streams one expert's weights past its own
rows and no gather of weights ever reaches HBM.

Inside a grid step the segment loop is `cadc_matmul`'s: each crossbar
segment's psum goes through f() and into a VMEM accumulator, and the tile
is written once (`_seg_psum`, `_acc_first`, `_flush`). No psum leaves
VMEM.

The row count is static (the bound `tile_bound` gives for every routing),
the live count is not: tiles past the live ones skip their dot under
`pl.when` and write zeros, and their index maps repeat the last live
blocks, so they fetch nothing. The cost follows the routed rows.

Forward only: training through it is not supported.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import dendritic
from repro.kernels.cadc_matmul import _acc_first, _flush, _seg_psum

Array = jnp.ndarray


def block_m_for(n_rows: int, n_groups: int) -> int:
    """Row tile for `n_rows` routed rows over `n_groups` experts: the rows
    an expert gets on average, as a power of two in [16, 256] (16 rows
    fill a bf16 tile; 256 fill the MXU's depth)."""
    per = -(-n_rows // max(n_groups, 1))
    b = 16
    while b < per and b < 256:
        b *= 2
    return b


def tile_bound(n_rows: int, n_groups: int, block_m: int) -> int:
    """Tiles enough for any split of `n_rows` rows into `n_groups` groups
    each padded to whole tiles: sum ceil(n_g / bm) <= (n + G (bm - 1)) / bm."""
    return max(1, (n_rows + n_groups * (block_m - 1)) // block_m)


def tile_layout(group_sizes: Array, block_m: int, n_tiles: int
                ) -> Tuple[Array, Array, Array]:
    """(first row of each group [G], expert of each tile [n_tiles], live
    tile count [1]). Tiles past the live ones name the last live tile's
    expert, so that they fetch no new weights."""
    tiles = (group_sizes + block_m - 1) // block_m
    ends = jnp.cumsum(tiles)
    n_live = ends[-1]
    i = jnp.minimum(jnp.arange(n_tiles), jnp.maximum(n_live - 1, 0))
    tile_expert = jnp.minimum(jnp.searchsorted(ends, i, side="right"),
                              group_sizes.shape[0] - 1)
    return ((ends - tiles) * block_m, tile_expert.astype(jnp.int32),
            n_live.reshape(1).astype(jnp.int32))


def _gmm_kernel(te_ref, nl_ref, x_ref, w_ref, o_ref, acc_ref, *, fn,
                n_seg: int, xbar: int):
    live = pl.program_id(0) < nl_ref[0]

    @pl.when(live)
    def _dot():
        for s in range(n_seg):
            fps = fn(_seg_psum(x_ref, w_ref, s, xbar))
            if s == 0:
                _acc_first(acc_ref, fps, False)
            else:
                acc_ref[...] += fps
        _flush(o_ref, acc_ref, False)

    @pl.when(jnp.logical_not(live))
    def _dead():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("crossbar_size", "fn", "block_m", "block_n",
                     "interpret"),
)
def cadc_gmm_pallas(x: Array, w: Array, tile_expert: Array, n_live: Array,
                    *, crossbar_size: int = 256, fn: str = "relu",
                    block_m: int = 256, block_n: int = 256,
                    interpret: bool = False) -> Array:
    """x [R, D] (R a multiple of block_m, D of crossbar_size) in the
    layout `tile_layout` describes; w [E, D, N]; tile_expert [R / block_m]
    and n_live [1] from `tile_layout`. Returns [R, N] in x's dtype, zeros
    in dead tiles."""
    r, d = x.shape
    n = w.shape[-1]
    bm, bn = block_m, block_n
    nj = n // bn

    def x_map(i, j, te, nl):
        return jnp.minimum(i, jnp.maximum(nl[0] - 1, 0)), 0

    def w_map(i, j, te, nl):
        return te[i], 0, jnp.where(i < nl[0], j, nj - 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(r // bm, nj),
        in_specs=[pl.BlockSpec((bm, d), x_map),
                  pl.BlockSpec((None, d, bn), w_map)],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, te, nl: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )
    body = functools.partial(_gmm_kernel, fn=dendritic.get(fn),
                             n_seg=d // crossbar_size, xbar=crossbar_size)
    return pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(tile_expert, n_live, x, w)


def cadc_gmm_xla(x: Array, w: Array, tile_expert: Array, n_live: Array, *,
                 crossbar_size: int = 256, fn: str = "relu",
                 block_m: int = 256) -> Array:
    """The kernel's oracle: the same tiles through an XLA einsum over each
    tile's gathered expert weights, psums materialised."""
    r, d = x.shape
    n = w.shape[-1]
    t, s = r // block_m, d // crossbar_size
    # operands widened to float32: exact for bf16 values, and float32
    # accumulation is what the MXU does
    xt = x.reshape(t, block_m, s, crossbar_size).astype(jnp.float32)
    wt = w.reshape(w.shape[0], s, crossbar_size, n)[tile_expert]
    psums = jnp.einsum("tmsk,tskn->tmsn", xt, wt.astype(jnp.float32),
                       precision=jax.lax.Precision.HIGHEST)
    y = jnp.sum(dendritic.get(fn)(psums), axis=2)
    live = jnp.arange(t) < n_live[0]
    return jnp.where(live[:, None, None], y, 0.0).reshape(r, n).astype(
        x.dtype)
