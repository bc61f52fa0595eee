"""GQA/MQA attention: RoPE, global-causal / sliding-local / bidirectional,
q-chunked blockwise softmax (bounded memory at 32k), KV-cache decode with
rolling window for local layers.

Decode supports PER-SLOT positions (`position` may be a scalar or a [B]
vector) — the continuous-batching serve engine runs every cache slot at its
own sequence offset. Two cache layouts share the same attention math:

  * dense `KVCache` [B, L, K, hd] — one contiguous ring per slot;
  * paged `PagedKV` — a pool of [n_blocks, block_size, K, hd] blocks plus a
    per-slot block table; `attention_decode_paged` dispatches through
    kernels.ops.paged_attention: on TPU the fused flash-decoding kernel
    consumes the block table directly (no ring materialization, dead
    blocks skipped), while the "xla" fallback gathers the blocks back into
    the ring layout before the (identical) masked SDPA — that path is
    bit-identical to the dense caches by construction and serves as the
    kernel's parity oracle. Q >= 1 tokens per step (multi-token append).

QKV/O projections route through layers.linear_apply, i.e. they are
CADC-partitioned when the config says so. The QK^T and AV products are
activation x activation — no weight crossbar — so CADC does not apply there
(DESIGN.md §4).
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
# Single definition site for the masking value and softcap form: the paged
# decode oracle (kernels/paged_attention.py) is bit-identical to this
# module's SDPA only while the two agree, so this module IMPORTS them —
# they cannot drift apart silently (kernels never import models, so the
# kernel module is the layering-clean home).
from repro.kernels.paged_attention import NEG_INF, _softcap
from repro.models.lm import layers as ll
from repro.parallel import act_sharding as sa

Array = jnp.ndarray


def attn_init(key, cfg: ArchConfig) -> Dict:
    kq, kk, kv, ko = jax.random.split(key, 4)
    d, h, k_, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    b = cfg.attn_qkv_bias
    p = {
        "wq": ll.linear_init(kq, d, h * hd, cfg, bias=b),
        "wk": ll.linear_init(kk, d, k_ * hd, cfg, bias=b),
        "wv": ll.linear_init(kv, d, k_ * hd, cfg, bias=b),
        "wo": ll.linear_init(ko, h * hd, d, cfg),
    }
    if cfg.qk_norm:
        p["q_norm"] = ll.rmsnorm_init(hd)
        p["k_norm"] = ll.rmsnorm_init(hd)
    return p


def _qk_norm(p, q: Array, k: Array, cfg: ArchConfig):
    """QK-norm: RMSNorm over head_dim on q and k, before rotary."""
    if not cfg.qk_norm:
        return q, k
    return (ll.rmsnorm_apply(p["q_norm"], q, cfg.norm_eps),
            ll.rmsnorm_apply(p["k_norm"], k, cfg.norm_eps))


def _hshard(t: Array, cfg: ArchConfig) -> Array:
    """Heads over the model axis (column-parallel QKV) when divisible;
    GQA archs with kv < axis keep k/v replicated (the guard drops it)."""
    return sa.shard_act(t, sa.U, sa.U, "model", sa.U,
                        enabled=cfg.act_sharding)


def _qkv(p, x, cfg: ArchConfig, positions: Array):
    b, s, _ = x.shape
    h, k_, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = _hshard(ll.linear_apply(p["wq"], x, cfg).reshape(b, s, h, hd), cfg)
    k = _hshard(ll.linear_apply(p["wk"], x, cfg).reshape(b, s, k_, hd), cfg)
    v = _hshard(ll.linear_apply(p["wv"], x, cfg).reshape(b, s, k_, hd), cfg)
    q, k = _qk_norm(p, q, k, cfg)
    q = ll.rope(q, positions, cfg.rope_theta)
    k = ll.rope(k, positions, cfg.rope_theta)
    return q, k, v


def _sdpa(q, k, v, mask, cfg: ArchConfig):
    """q [B,C,H,hd], k/v [B,L,K,hd], mask [B?,C,L] bool (True=keep)."""
    bq, c, h, hd = q.shape
    k_ = k.shape[2]
    g = h // k_
    qg = q.reshape(bq, c, k_, g, hd)
    scores = jnp.einsum("bckgd,blkd->bkgcl", qg, k,
                        preferred_element_type=jnp.float32)
    scores = _softcap(scores * (hd ** -0.5), cfg.attn_logit_softcap)
    scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgcl,blkd->bckgd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(bq, c, h, hd).astype(q.dtype)


def attention_train(
    p: Dict, x: Array, cfg: ArchConfig, *, kind: str, positions: Array
) -> Array:
    """kind: 'global' (causal, or bidirectional for encoders) | 'local'
    (causal sliding window). q is processed in cfg.attn_chunk chunks via
    lax.scan — bounded score memory at 32k.
    """
    out, _, _ = _attention_full(p, x, cfg, kind=kind, positions=positions)
    return out


def attention_prefill(
    p: Dict, x: Array, cfg: ArchConfig, *, kind: str, positions: Array
) -> Tuple[Array, Tuple[Array, Array]]:
    """Batched-prefill attention: the full-sequence forward of
    attention_train, additionally returning the rope'd (k, v)
    [B, S, K, hd] so the serve engine can insert them into KV caches
    (dense or paged) without re-running the projections."""
    out, k, v = _attention_full(p, x, cfg, kind=kind, positions=positions)
    return out, (k, v)


def _attention_full(
    p: Dict, x: Array, cfg: ArchConfig, *, kind: str, positions: Array
) -> Tuple[Array, Array, Array]:
    b, s, d = x.shape
    q, k, v = _qkv(p, x, cfg, positions)
    chunk = min(cfg.attn_chunk, s)
    if s % chunk != 0:  # ragged tail: fall back to one chunk
        chunk = s
    n_chunks = s // chunk
    w = cfg.local_window

    # cfg.attn_unroll (audit mode): a lax.scan body is priced ONCE by XLA's
    # cost analysis, so the roofline audit unrolls the q-chunk loop (same
    # math/blocking — only the loop structure changes).
    def _chunks(body):
        if cfg.attn_unroll:
            outs = [body(None, ci)[1] for ci in range(n_chunks)]
            return jnp.stack(outs, axis=0)
        _, outs = jax.lax.scan(body, None, jnp.arange(n_chunks))
        return outs

    if kind == "local" and s > w + chunk:
        # keys restricted to a static window slice per q-chunk
        def body(carry, ci):
            q_c = jax.lax.dynamic_slice_in_dim(q, ci * chunk, chunk, axis=1)
            start = jnp.maximum(ci * chunk - w, 0)
            k_c = jax.lax.dynamic_slice_in_dim(k, start, w + chunk, axis=1)
            v_c = jax.lax.dynamic_slice_in_dim(v, start, w + chunk, axis=1)
            qpos = ci * chunk + jnp.arange(chunk)
            kpos = start + jnp.arange(w + chunk)
            mask = (kpos[None, :] <= qpos[:, None]) & (
                kpos[None, :] > qpos[:, None] - w
            )
            o = _sdpa(q_c, k_c, v_c, jnp.broadcast_to(mask, (b, chunk, w + chunk)),
                      cfg)
            return carry, o

        out = jnp.moveaxis(_chunks(body), 0, 1).reshape(b, s, -1)
    else:
        def body(carry, ci):
            q_c = jax.lax.dynamic_slice_in_dim(q, ci * chunk, chunk, axis=1)
            qpos = ci * chunk + jnp.arange(chunk)
            kpos = jnp.arange(s)
            if cfg.is_encoder:
                mask = jnp.ones((chunk, s), bool)
            else:
                mask = kpos[None, :] <= qpos[:, None]
                if kind == "local":
                    mask &= kpos[None, :] > qpos[:, None] - w
            o = _sdpa(q_c, k, v, jnp.broadcast_to(mask, (b, chunk, s)), cfg)
            return carry, o

        out = jnp.moveaxis(_chunks(body), 0, 1).reshape(b, s, -1)

    return ll.linear_apply(p["wo"], out, cfg), k, v


# ---------------------------------------------------------------------------
# decode path (KV cache)
# ---------------------------------------------------------------------------

class KVCache(NamedTuple):
    k: Array  # [B, L, K, hd] — L = seq_len (global) or window (local)
    v: Array


class PagedKV(NamedTuple):
    """Paged KV pool: a slot's logical [L, K, hd] ring is scattered over
    `L / block_size` physical blocks named by its block-table row."""

    k: Array  # [n_blocks, block_size, K, hd]
    v: Array


def cache_len(cfg: ArchConfig, kind: str, seq_len: int, *,
              headroom: int = 0) -> int:
    """Logical per-slot cache length for an attention layer kind. The
    single source of the ring geometry — both the dense caches and the
    paged block math derive from it (bit-parity depends on agreement).

    `headroom` buys multi-token appends (speculative-decode drafts of
    Q = headroom + 1 tokens) sequential-exact semantics on local rings: a
    Q-token append is bitwise the sequential decode only while no write
    lands inside an earlier q-token's window, which needs
    ring_len >= window + Q - 1 (see attention_decode_paged). Entries past
    the window are mask-invalid either way, so a headroomed ring changes
    capacity, never attention output."""
    if kind == "local":
        return min(cfg.local_window + headroom, seq_len)
    return seq_len


def init_cache(cfg: ArchConfig, kind: str, batch: int, seq_len: int,
               dtype) -> KVCache:
    l = cache_len(cfg, kind, seq_len)
    shape = (batch, l, cfg.n_kv_heads, cfg.head_dim)
    return KVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))


def init_paged_pool(cfg: ArchConfig, n_blocks: int, block_size: int,
                    dtype) -> PagedKV:
    shape = (n_blocks, block_size, cfg.n_kv_heads, cfg.head_dim)
    return PagedKV(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))


def _decode_qkv(p: Dict, x: Array, cfg: ArchConfig, position: Array):
    """Shared decode projections. x [B, Q, d] (Q == 1 for the ordinary
    step, Q > 1 for multi-token append); position scalar or [B] is the
    BASE position — token t sits at position + t. Returns pos [B]."""
    b, s = x.shape[0], x.shape[1]
    h, k_, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = ll.linear_apply(p["wq"], x, cfg).reshape(b, s, h, hd)
    k_new = ll.linear_apply(p["wk"], x, cfg).reshape(b, s, k_, hd)
    v_new = ll.linear_apply(p["wv"], x, cfg).reshape(b, s, k_, hd)
    pos = jnp.broadcast_to(jnp.asarray(position, jnp.int32), (b,))
    qpos = pos[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
    q, k_new = _qk_norm(p, q, k_new, cfg)
    q = ll.rope(q, qpos, cfg.rope_theta)
    k_new = ll.rope(k_new, qpos, cfg.rope_theta)
    return q, k_new, v_new, pos


def _ring_slot(pos: Array, l: int, kind: str) -> Array:
    """Ring index each slot's new token lands at. Global caches clamp at
    l-1 (mirrors the old dynamic_update_slice saturation at overflow)."""
    return (pos % l) if kind == "local" else jnp.clip(pos, 0, l - 1)


def _decode_mask(pos: Array, l: int, kind: str, window: int) -> Array:
    """[B, L] validity of ring entries at per-slot positions `pos` [B]."""
    idx = jnp.arange(l)[None, :]
    p = pos[:, None]
    if kind == "local":
        # rolling buffer: entry i holds absolute position p_i with
        # p_i ≡ i (mod l) and p_i <= pos; valid iff pos - p_i < window
        abs_pos = p - ((p - idx) % l)
        return (abs_pos >= 0) & (abs_pos <= p) & (abs_pos > p - window)
    return idx <= p


def attention_decode(
    p: Dict, x: Array, cfg: ArchConfig, *, kind: str, position: Array,
    cache: KVCache,
) -> Tuple[Array, KVCache]:
    """One-token decode. x [B, 1, d]; position int32 — a scalar (legacy
    fixed-batch serving: every row at the same index) or a [B] vector
    (continuous batching: per-slot offsets). Local layers use a rolling
    (mod-window) cache."""
    b = x.shape[0]
    q, k_new, v_new, pos = _decode_qkv(p, x, cfg, position)

    l = cache.k.shape[1]
    slot = _ring_slot(pos, l, kind)  # kind is static
    rows = jnp.arange(b)
    k_c = cache.k.at[rows, slot].set(k_new[:, 0].astype(cache.k.dtype))
    v_c = cache.v.at[rows, slot].set(v_new[:, 0].astype(cache.v.dtype))

    valid = _decode_mask(pos, l, kind, cfg.local_window)
    out = _sdpa(q, k_c, v_c, valid[:, None, :], cfg).reshape(b, 1, -1)
    return ll.linear_apply(p["wo"], out, cfg), KVCache(k_c, v_c)


def attention_decode_paged(
    p: Dict, x: Array, cfg: ArchConfig, *, kind: str, position: Array,
    cache: PagedKV, block_table: Array, ring_len: Optional[int] = None,
) -> Tuple[Array, PagedKV]:
    """Decode against the paged pool. x [B, Q, d] with Q >= 1 (Q == 1 is
    the ordinary serve step; Q > 1 is multi-token append — speculative-
    decode drafts). block_table [B, nb] int32 maps each slot's logical
    block index to a physical block; -1 marks an unallocated block (writes
    to it are dropped, reads are masked). The table may be a COVERED-
    PREFIX slice of the full table (the serve engine's dead-block
    skipping); `ring_len` then carries the true ring geometry for the
    mod/clip ring math (default: nb * block_size, the full-table case).

    Q > 1 ring semantics are the `backends._ring_vals` ones (batched
    prefill uses the same): ALL Q tokens' K/V are written first
    (newest-wins per ring entry), then every q-token attends the final
    ring state under its own causal/window mask. On a LOCAL ring this is
    exactly sequential decode only while the append does not wrap the
    ring (base position + Q <= ring_len, i.e. window + Q tokens of
    drafting headroom): a wrapping append overwrites entries still inside
    the earliest draft tokens' window, and those tokens then mask the
    overwritten entries instead of seeing their old content
    (tests/test_paged_attention.py pins both the no-wrap equality and the
    wrap-case masking). 'global' appends are sequential-exact always.

    The attention itself runs through kernels.ops.paged_attention: the
    fused flash-decoding Pallas kernel consumes the block table directly
    on TPU ("auto"/"pallas"; dead chunks cost zero MXU work), while the
    "xla" fallback is the gather formulation — blocks regathered into the
    ring layout before the same masked SDPA as `attention_decode`, which
    keeps the paged path bit-identical to the dense path by construction
    (the CI parity gate). cfg.paged_attn_impl selects; the fused kernel is
    parity-gated against the gather oracle in tests/test_paged_attention.
    """
    from repro.kernels import ops as kops

    b, q_len = x.shape[0], x.shape[1]
    q, k_new, v_new, pos = _decode_qkv(p, x, cfg, position)

    n_blocks, bs = cache.k.shape[0], cache.k.shape[1]
    nb = block_table.shape[1]
    if ring_len is None:
        ring_len = nb * bs
    if q_len > ring_len:
        # two q-tokens would map to the SAME ring entry and the
        # duplicate-index scatter's winner is unspecified — fail fast
        # instead of writing a nondeterministic cache
        raise ValueError(
            f"multi-token append of {q_len} tokens exceeds the "
            f"{ring_len}-entry ring: ring slots would collide")
    # ring slots of the Q appended tokens: [B, Q] (distinct: Q <= ring_len)
    qpos = pos[:, None] + jnp.arange(q_len, dtype=jnp.int32)[None, :]
    slot = _ring_slot(qpos, ring_len, kind)
    blk, off = slot // bs, slot % bs
    phys = jnp.take_along_axis(block_table, blk, axis=1)
    # unallocated (-1) -> out-of-range sentinel, dropped by the scatter
    phys_w = jnp.where(phys >= 0, phys, n_blocks)
    k_pool = cache.k.at[phys_w, off].set(
        k_new.astype(cache.k.dtype), mode="drop")
    v_pool = cache.v.at[phys_w, off].set(
        v_new.astype(cache.v.dtype), mode="drop")

    out = kops.paged_attention(
        q, k_pool, v_pool, block_table, pos, kind=kind,
        window=cfg.local_window, ring_len=ring_len,
        softcap=cfg.attn_logit_softcap, impl=cfg.paged_attn_impl,
    ).reshape(b, q_len, -1)
    return ll.linear_apply(p["wo"], out, cfg), PagedKV(k_pool, v_pool)
