"""Public jit'd wrappers: Pallas kernel on TPU, XLA path elsewhere.

`impl` resolution:
  * "pallas"     — pl.pallas_call compiled for TPU (requires TPU backend)
  * "interpret"  — Pallas interpret mode (CPU correctness path / CI)
  * "xla"        — core.cadc einsum formulation (always available; the
                   distribution layer uses this: it shards cleanly)
  * "auto"       — pallas on TPU, xla otherwise

Every impl is gradient-aware: the Pallas paths carry jax.custom_vjp rules
(backward kernels, see kernels/cadc_matmul.py) so `impl="auto"` is valid
under jax.grad on every backend — training no longer needs to detour
through the XLA einsum path, which now serves as the autodiff reference
oracle for the fused kernels.

`save_gate` selects the gradient-residual format of the Pallas paths
("auto" | "packed" | "bytes" | "recompute" — see kernels/cadc_matmul.py);
the XLA path ignores it (XLA autodiff rematerializes its own residuals).

Invariants the dispatch preserves (docs/kernels.md):
  * q8 ops are BIT-exact across impls — every path accumulates segments
    sequentially in the oracle's order, so "interpret"/"pallas" vs "xla"
    is numerics-transparent, not merely allclose.
  * paged_attention's "xla" path is the gather oracle: bit-identical to
    the dense ring caches by construction (the serve CI parity gate),
    while the fused kernel skips dead/garbage blocks so they contribute
    EXACTLY 0 (never "0 * garbage" — NaN-proof) and is parity-gated
    against the oracle. Q >= 1 multi-token appends (speculative drafts)
    follow the ring-wrap semantics pinned in attention_decode_paged.
  * float kernels auto-re-block D under their VMEM budget with unchanged
    accumulation order — chunked == unchunked bitwise.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core import cadc as _core
from repro.kernels import cadc_conv as _ck
from repro.kernels import cadc_matmul as _pk

Array = jnp.ndarray


def _resolve(impl: str) -> str:
    if impl != "auto":
        return impl
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def cadc_matmul(
    x: Array,
    w: Array,
    *,
    crossbar_size: int = 256,
    fn: str = "relu",
    impl: str = "auto",
    block_m: int = 256,
    block_n: int = 256,
    save_gate: str = "auto",
    vmem_budget_bytes: int = _pk.FWD_VMEM_BUDGET,
) -> Array:
    """y = sum_s f(x_s @ w_s). Output in x.dtype (xla) / fp32 (pallas).
    The Pallas forward auto-re-blocks D over a grid axis when its resident
    strips would exceed `vmem_budget_bytes` (bit-identical result)."""
    mode = _resolve(impl)
    if mode == "xla":
        return _core.cadc_matmul(x, w, crossbar_size=crossbar_size, fn=fn)
    return _pk.cadc_matmul_pallas(
        x,
        w,
        crossbar_size=crossbar_size,
        fn=fn,
        block_m=block_m,
        block_n=block_n,
        interpret=(mode == "interpret"),
        save_gate=save_gate,
        vmem_budget_bytes=vmem_budget_bytes,
    ).astype(x.dtype)


def cadc_matmul_q8(
    x_q: Array,
    w_codes: Array,
    scale: Array,
    *,
    crossbar_size: int = 256,
    fn: str = "relu",
    impl: str = "auto",
    block_m: int = 256,
    block_n: int = 256,
    save_gate: str = "auto",
    vmem_budget_bytes: int = _pk.FWD_VMEM_BUDGET,
) -> Array:
    mode = _resolve(impl)
    if mode == "xla":
        from repro.kernels import ref

        return ref.cadc_matmul_q8_ref(
            x_q, w_codes, scale, crossbar_size=crossbar_size, fn=fn
        )
    return _pk.cadc_matmul_q8_pallas(
        x_q,
        w_codes,
        scale,
        crossbar_size=crossbar_size,
        fn=fn,
        block_m=block_m,
        block_n=block_n,
        interpret=(mode == "interpret"),
        save_gate=save_gate,
        vmem_budget_bytes=vmem_budget_bytes,
    )


def paged_attention(
    q: Array,
    k_pool: Array,
    v_pool: Array,
    block_table: Array,
    positions: Array,
    *,
    kind: str,
    window: int,
    ring_len=None,
    softcap=None,
    impl: str = "auto",
) -> Array:
    """Paged-attention decode over block-table-indexed K/V pools.

    q [B, Q, H, hd] (rope'd), pools [n_blocks, bs, K, hd], block_table
    [B, nb] int32 (-1 = unallocated), positions [B]. Q >= 1 (multi-token
    append). Same impl resolution as cadc_matmul: "pallas" / "interpret"
    run the fused flash-decoding kernel (block table consumed directly,
    dead chunks skipped); "xla" is the gather formulation — the PR 3
    decode math, kept as the oracle/fallback so the CPU path stays
    bit-identical to the dense cache layout.
    """
    from repro.kernels import paged_attention as _pa

    mode = _resolve(impl)
    if mode == "xla":
        return _pa.paged_attention_xla(
            q, k_pool, v_pool, block_table, positions, kind=kind,
            window=window, ring_len=ring_len, softcap=softcap,
        )
    return _pa.paged_attention_pallas(
        q, k_pool, v_pool, block_table, positions, kind=kind,
        window=window, ring_len=ring_len, softcap=softcap,
        interpret=(mode == "interpret"),
    )


def _conv_fmap_vmem_bytes(
    x_shape: Tuple[int, ...],
    w_shape: Tuple[int, ...],
    padding,
    itemsize: int = 4,
) -> int:
    """VMEM bytes of ONE padded feature map held resident by the fused conv
    kernel — computed from the REAL normalized padding (a "SAME" 1x1 conv
    pads nothing; "VALID" never pads), not the worst-case (k-1) halo the
    old estimate assumed."""
    from repro.core.conv import _norm_padding

    _, h, w, cin = x_shape
    k1, k2 = w_shape[0], w_shape[1]
    (pt, pb), (pl_, pr) = _norm_padding(padding, (k1, k2), (1, 1))
    return (h + pt + pb) * (w + pl_ + pr) * cin * itemsize


def cadc_conv2d(
    x: Array,
    w: Array,
    *,
    crossbar_size: int = 256,
    fn: str = "relu",
    stride=(1, 1),
    padding="SAME",
    impl: str = "auto",
    block_h: int = 8,
    block_n: int = 128,
    vmem_budget_bytes: int = _ck.FMAP_VMEM_BUDGET,
    save_gate: str = "auto",
) -> Array:
    """Fused im2col + segmented conv (psums and patches never hit HBM).

    Falls back to the XLA im2col path when the padded feature map would not
    fit the kernel's VMEM budget, the batch is empty (a zero-size Pallas
    grid is not a meaningful launch), or dilation is needed.
    """
    from repro.core import conv as _conv

    mode = _resolve(impl)
    fmap_bytes = _conv_fmap_vmem_bytes(
        x.shape, w.shape, padding, jnp.dtype(x.dtype).itemsize
    )
    if mode == "xla" or x.shape[0] == 0 or fmap_bytes > vmem_budget_bytes:
        return _conv.cadc_conv2d(
            x, w, crossbar_size=crossbar_size, fn=fn, stride=stride,
            padding=padding,
        )
    return _ck.cadc_conv2d_pallas(
        x, w, crossbar_size=crossbar_size, fn=fn, stride=tuple(stride),
        padding=padding, block_h=block_h, block_n=block_n,
        interpret=(mode == "interpret"), save_gate=save_gate,
    ).astype(x.dtype)


def cadc_conv2d_q8(
    x_q: Array,
    w_codes: Array,
    scale: Array,
    *,
    crossbar_size: int = 256,
    fn: str = "relu",
    stride=(1, 1),
    padding="SAME",
    impl: str = "auto",
    block_h: int = 8,
    block_n: int = 128,
    vmem_budget_bytes: int = _ck.FMAP_VMEM_BUDGET,
    save_gate: str = "auto",
) -> Array:
    """Quantized fused conv (int8 taps -> int32 psums -> dequant -> f()).

    The XLA path IS the sequential q8 oracle (ref.cadc_conv2d_q8_ref), so
    "interpret"/"pallas" vs "xla" agree bit-exactly — the dispatch is
    numerics-transparent. Same VMEM fallback rules as cadc_conv2d (the
    int8 fmap is 4x denser, so the fused path engages at 4x the spatial
    size)."""
    from repro.kernels import ref

    mode = _resolve(impl)
    fmap_bytes = _conv_fmap_vmem_bytes(
        x_q.shape, w_codes.shape, padding, jnp.dtype(x_q.dtype).itemsize
    )
    if mode == "xla" or x_q.shape[0] == 0 or fmap_bytes > vmem_budget_bytes:
        return ref.cadc_conv2d_q8_ref(
            x_q, w_codes, scale, crossbar_size=crossbar_size, fn=fn,
            stride=stride, padding=padding,
        )
    return _ck.cadc_conv2d_q8_pallas(
        x_q, w_codes, scale, crossbar_size=crossbar_size, fn=fn,
        stride=tuple(stride), padding=padding, block_h=block_h,
        block_n=block_n, interpret=(mode == "interpret"),
        save_gate=save_gate,
    )
