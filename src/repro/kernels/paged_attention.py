"""Pallas TPU kernel: gather-free paged-attention decode (flash-decoding
over block tables).

The serve engine's paged KV cache keeps every slot's logical [L, K, hd]
ring scattered over `[n_blocks, block_size, K, hd]` pools, named by a
per-slot block table. PR 3's decode path gathered each slot's blocks back
into the dense ring layout before SDPA — correct (bit-identical to the
dense caches by construction) but wasteful: every decode step materializes
a full [B, L, K, hd] copy of the rings in HBM just to read it once.

This kernel consumes the block table DIRECTLY, the same design move the
CADC matmuls make for crossbar psums: partial results never round-trip
through buffers. Layout:

  * grid (slots, kv_heads, block_chunks) — one chunk = one logical block
    of the slot's ring; the chunk axis is "arbitrary" (sequential), slots
    and kv-heads parallel.
  * the K/V pool blocks are fetched straight from the pools through the
    block table via scalar-prefetch index maps
    (pltpu.PrefetchScalarGridSpec): block c of slot b loads physical block
    `table[b, c]` — no gather, no ring materialization.
  * online softmax: running max / normalizer / weighted-value accumulator
    live in VMEM scratch across the chunk axis; the output tile is written
    once, after the last chunk.
  * dead chunks cost nothing: a chunk whose table entry is -1 (unallocated
    / evicted) or whose ring positions are all outside the validity window
    is skipped under `pl.when` — zero MXU work, and garbage blocks
    contribute EXACTLY 0 to the output (they are never touched, rather
    than being multiplied by underflowed-to-zero softmax weights).
  * GQA: the whole q-head group of a kv head stays resident per grid step
    (q is pre-shaped [B, K, q_len * group, hd]); MQA/MHA are the group
    sizes H and 1 of the same layout.
  * q_len >= 1: multi-token append (speculative-decode drafts) uses the
    same kernel. Ring semantics follow backends._ring_vals: entry i holds
    the NEWEST position congruent to i, so q-token t (absolute position
    pos + t) masks entries whose held position exceeds pos + t. On a
    local ring this equals sequential decode exactly UNLESS the append
    wraps the ring (pos + q_len > ring_len): a wrapping append
    overwrites entries still inside the earliest tokens' window, and
    those tokens mask the overwritten entries rather than seeing their
    pre-append content (attention.attention_decode_paged docstring).

`paged_attention_xla` is the gather formulation demoted to oracle /
fallback: it reproduces the PR 3 decode math exactly (NEG_INF masking,
identical einsum forms), so the CPU serving path — and the CI bit-parity
gate against the dense backend — are unchanged, while the kernel is
parity-gated against it in interpret mode (tests/test_paged_attention.py).

Ring-validity mask (shared by both implementations)
---------------------------------------------------
For q-token t of a slot at base position `pos` (absolute position
qp = pos + t), ring entry i (l = ring_len) is valid iff

  global:  i <= qp                                  (entries hold p_i = i)
  local:   p_i = P - ((P - i) mod l)  with  P = pos + q_len - 1
           valid iff 0 <= p_i <= qp  and  p_i > qp - window

— for q_len == 1 this is exactly attention._decode_mask. Entries of
blocks with table entry -1 are always invalid.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jnp.ndarray

# THE masking value of the attention stack (models/lm/attention.py imports
# it from here): finite, so masked scores underflow to exact-0 softmax
# weight instead of producing NaNs on all-masked (idle-slot) rows. The
# oracle's bit-parity with the dense decode path depends on both layers
# using this one definition.
NEG_INF = -2.0 ** 30


def _softcap(scores: Array, cap: Optional[float]) -> Array:
    """Logit softcap shared by the SDPA layers and the paged kernels —
    one form, imported everywhere (see NEG_INF note)."""
    if cap is None:
        return scores
    return cap * jnp.tanh(scores / cap)


def _ring_mask(pos: Array, idx: Array, *, kind: str, ring_len: int,
               window: int, q_len: int) -> Array:
    """[q_len, n_idx] validity of ring entries `idx` (int32 [1, n_idx] or
    [n_idx]) for the q tokens of a slot at base position `pos` (scalar).
    The single source of the paged mask — kernel, oracle and tests all
    call it (parity depends on agreement)."""
    idx = idx.reshape(1, -1)
    qp = pos + jax.lax.broadcasted_iota(jnp.int32, (q_len, idx.shape[1]), 0)
    if kind == "local":
        newest = pos + q_len - 1
        held = newest - ((newest - idx) % ring_len)
        return (held >= 0) & (held <= qp) & (held > qp - window)
    return idx <= qp


# ---------------------------------------------------------------------------
# oracle / fallback: the gather formulation (PR 3 decode math, generalized
# to q_len >= 1)
# ---------------------------------------------------------------------------

def paged_attention_xla(
    q: Array,
    k_pool: Array,
    v_pool: Array,
    block_table: Array,
    positions: Array,
    *,
    kind: str,
    window: int,
    ring_len: Optional[int] = None,
    softcap: Optional[float] = None,
) -> Array:
    """Gather path: blocks -> dense ring layout -> masked SDPA.

    q [B, Q, H, hd] (rope'd), pools [n_blocks, bs, K, hd], block_table
    [B, nb] int32 (-1 = unallocated; may be a COVERED-PREFIX slice of the
    full table, in which case ring_len carries the true ring geometry),
    positions [B] int32 base position per slot. Returns [B, Q, H, hd] in
    q.dtype — for q_len == 1 bit-identical to the PR 3
    attention_decode_paged math by construction.
    """
    b, q_len, h, hd = q.shape
    bs, k_ = k_pool.shape[1], k_pool.shape[2]
    nb = block_table.shape[1]
    l_eff = nb * bs
    if ring_len is None:
        ring_len = l_eff
    g = h // k_

    tbl = jnp.maximum(block_table, 0)          # garbage reads get masked
    k_c = k_pool[tbl].reshape(b, l_eff, k_, hd)
    v_c = v_pool[tbl].reshape(b, l_eff, k_, hd)

    idx = jnp.arange(l_eff, dtype=jnp.int32)
    valid = jax.vmap(
        lambda p: _ring_mask(p, idx, kind=kind, ring_len=ring_len,
                             window=window, q_len=q_len)
    )(positions.astype(jnp.int32))             # [B, Q, l_eff]
    valid &= jnp.repeat(block_table >= 0, bs, axis=1)[:, None, :]

    # identical einsum forms / mask order / casts as attention._sdpa
    qg = q.reshape(b, q_len, k_, g, hd)
    scores = jnp.einsum("bckgd,blkd->bkgcl", qg, k_c,
                        preferred_element_type=jnp.float32)
    scores = _softcap(scores * (hd ** -0.5), softcap)
    scores = jnp.where(valid[:, None, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgcl,blkd->bckgd", probs.astype(v_c.dtype), v_c,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, q_len, h, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# fused kernel
# ---------------------------------------------------------------------------

def _flash_kernel(tbl_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, nb: int, bs: int, ring_len: int,
                  window: int, kind: str, q_len: int, scale: float,
                  softcap: Optional[float]):
    """One grid step = one (slot, kv-head, ring-block) triple.

    Scratch rows are the q-head group of this kv head ([q_len * g, ...]);
    they persist over the chunk axis (innermost, "arbitrary") and reset at
    chunk 0. m/l are [qg, 1] fp32 (running max / normalizer), acc [qg, hd].
    """
    b = pl.program_id(0)
    c = pl.program_id(2)
    qg, hd = acc_scr.shape
    g = qg // q_len

    @pl.when(c == 0)
    def _reset():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    pos = pos_ref[b]
    idx = c * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
    mask = _ring_mask(pos, idx, kind=kind, ring_len=ring_len,
                      window=window, q_len=q_len)           # [q_len, bs]
    live = (tbl_ref[b, c] >= 0) & jnp.any(mask)

    @pl.when(live)
    def _chunk():
        qt = q_ref[0, 0].astype(jnp.float32)                # [qg, hd]
        k = k_ref[0, :, 0].astype(jnp.float32)              # [bs, hd]
        v = v_ref[0, :, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            qt, k, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                           # [qg, bs]
        s = _softcap(s, softcap)
        s = jnp.where(jnp.repeat(mask, g, axis=0), s, -jnp.inf)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # first live chunk: m_prev = -inf and the rescale factor is 0
        # (never nan — m_new is finite whenever any mask row is live; rows
        # whose every chunk is masked keep m = -inf and l = 0 and resolve
        # to 0 output in _flush).
        alpha = jnp.where(jnp.isfinite(m_prev),
                          jnp.exp(m_prev - m_new), 0.0)
        p = jnp.exp(s - m_new)
        p = jnp.where(jnp.isfinite(m_new), p, 0.0)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = alpha * acc_scr[...] + jax.lax.dot_general(
            p, v, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[...] = m_new

    @pl.when(c == nb - 1)
    def _flush():
        l = l_scr[...]
        safe = jnp.where(l > 0, l, 1.0)
        o_ref[0, 0] = jnp.where(l > 0, acc_scr[...] / safe, 0.0)


@functools.partial(
    jax.jit,
    static_argnames=("kind", "window", "ring_len", "softcap", "interpret"),
)
def paged_attention_pallas(
    q: Array,
    k_pool: Array,
    v_pool: Array,
    block_table: Array,
    positions: Array,
    *,
    kind: str,
    window: int,
    ring_len: Optional[int] = None,
    softcap: Optional[float] = None,
    interpret: bool = False,
) -> Array:
    """Fused flash-decoding over the block table. Same contract as
    paged_attention_xla; output fp32 accumulated, cast back to q.dtype.

    Unallocated (-1) and fully-invalid chunks are skipped under pl.when —
    evicted/garbage blocks cost zero MXU work and contribute exactly 0.
    """
    b, q_len, h, hd = q.shape
    n_blocks, bs, k_, _ = k_pool.shape
    nb = block_table.shape[1]
    if ring_len is None:
        ring_len = nb * bs
    g = h // k_
    qg = q_len * g

    # q-head group resident per kv head: [B, K, q_len * g, hd]
    qt = jnp.transpose(q.reshape(b, q_len, k_, g, hd), (0, 2, 1, 3, 4))
    qt = qt.reshape(b, k_, qg, hd)
    # The RAW table is the scalar-prefetch operand — the kernel's per-chunk
    # liveness test needs the -1 sentinels. Only the FETCH index map clamps
    # (a dead chunk still names some block for the pipelined load; the
    # kernel never computes on it).
    tbl = jnp.asarray(block_table, jnp.int32)
    pos = jnp.broadcast_to(jnp.asarray(positions, jnp.int32), (b,))

    def _kv_index(b_, h_, c, tbl_, pos_):
        return (jnp.maximum(tbl_[b_, c], 0), 0, h_, 0)

    out = pl.pallas_call(
        functools.partial(
            _flash_kernel, nb=nb, bs=bs, ring_len=ring_len, window=window,
            kind=kind, q_len=q_len, scale=hd ** -0.5, softcap=softcap,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, k_, nb),
            in_specs=[
                pl.BlockSpec((1, 1, qg, hd),
                             lambda b_, h_, c, tbl_, pos_: (b_, h_, 0, 0)),
                pl.BlockSpec((1, bs, 1, hd), _kv_index),
                pl.BlockSpec((1, bs, 1, hd), _kv_index),
            ],
            out_specs=pl.BlockSpec((1, 1, qg, hd),
                                   lambda b_, h_, c, tbl_, pos_:
                                   (b_, h_, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((qg, 1), jnp.float32),
                pltpu.VMEM((qg, 1), jnp.float32),
                pltpu.VMEM((qg, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, k_, qg, hd), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(tbl, pos, qt, k_pool, v_pool)

    out = out.reshape(b, k_, q_len, g, hd)
    return jnp.transpose(out, (0, 2, 1, 3, 4)).reshape(
        b, q_len, h, hd).astype(q.dtype)
