"""Device-side cache backends for the serve engine.

Both backends expose the same four jitted programs —

    decode(params, caches, tables, tokens, positions) -> (next, logits, caches)
    write_prefill(caches, contribs, slot_ids, lengths, tables) -> caches
    reset_slots(caches, slot_mask) -> caches
    init_caches() -> caches

— `decode` also keeps its program's MoE routing in `routed` (the rows
routed to each held expert and each token's selected experts in each MoE
layer, `transformer._routed_rows`, or () without experts), for the engine
to fetch with the tokens — and the paged
backend built with spec_tokens=K adds the speculative
draft/verify program

    decode_spec(params, caches, tables, tokens [B, K+1], positions)
        -> (greedy [B, K+1], logits, keep [B], caches)

which scores K drafts + the committed token in one multi-token append,
computes the accepted-prefix length in-trace, and rolls recurrent-layer
states back to the last kept token (KV entries of rejected drafts need
no rollback — the next append rewrites them before any read). Rings get
window+K (local) / max_len+K (global) headroom so drafts stay in the
sequential-exact append regime (attention.cache_len).

`DenseBackend` keeps the classic per-slot ring caches ([n_slots, L, K, hd]);
`PagedBackend` scatters each ring over block-table-indexed pools. The two
are bit-identical on the decode path by construction: the paged writer
places exactly the entries the dense ring holds, and the paged attention
gathers them back into the ring layout before the same masked SDPA
(attention.attention_decode_paged). That invariant is the acceptance test
of the subsystem (tests/test_serve_engine.py).

Prefill-cache insertion uses a GATHER formulation, not a scatter over
token positions: ring entry i of a slot with prompt length `len` holds the
latest position p_i ≡ i (mod L) with p_i <= len-1 — computed directly, so
rolling local windows need no duplicate-index scatter (whose write order
XLA leaves unspecified).
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.launch import steps as steps_lib
from repro.models.lm import attention as attn
from repro.models.lm import transformer as tf

Array = jnp.ndarray


# ---------------------------------------------------------------------------
# pytree walking keyed by layer kind
# ---------------------------------------------------------------------------

def map_layer_caches(caches, contribs, cfg: ArchConfig,
                     fn: Callable[[str, bool, Any, Any], Any]):
    """Apply fn(kind, stacked, cache_subtree, contrib_subtree) per layer
    position of the {'lead'?, 'units', 'tail'} cache pytree. contribs may
    be None (fn then receives None)."""
    reps, pattern, tail = tf.layout(cfg)
    lead = tf.lead_kinds(cfg)
    c_units = contribs["units"] if contribs is not None else None
    c_tail = contribs["tail"] if contribs is not None else None
    c_lead = contribs.get("lead") if contribs is not None else None
    units = tuple(
        fn(pattern[j], True, caches["units"][j],
           c_units[j] if c_units is not None else None)
        for j in range(len(caches["units"]))
    )
    tails = tuple(
        fn(tail[i], False, caches["tail"][i],
           c_tail[i] if c_tail is not None else None)
        for i in range(len(caches["tail"]))
    )
    out = {"units": units, "tail": tails}
    if lead:
        out["lead"] = tuple(
            fn(kind, False, caches["lead"][i],
               c_lead[i] if c_lead is not None else None)
            for i, kind in enumerate(lead))
    return out


def _ring_vals(kv: Array, lengths: Array, ring_len: int
               ) -> Tuple[Array, Array]:
    """Gather the ring layout out of full-prompt K/V.

    kv [Bp, S, ...], lengths [Bp] -> (vals [Bp, ring_len, ...],
    valid [Bp, ring_len]). Entry i holds prompt position
    p_i = last - ((last - i) mod ring_len) (the newest position congruent
    to i), invalid when that underflows — identical to what token-by-token
    decode writes would have left behind."""
    s = kv.shape[1]
    last = (lengths - 1)[:, None]                       # [Bp, 1]
    i = jnp.arange(ring_len)[None, :]
    p = last - ((last - i) % ring_len)
    valid = (p >= 0) & (p <= last)
    pc = jnp.clip(p, 0, s - 1)
    idx = pc.reshape(pc.shape + (1,) * (kv.ndim - 2))
    vals = jnp.take_along_axis(kv, idx, axis=1)
    return vals, valid


def _mask_rows(mask: Array, like: Array, axis: int) -> Array:
    shape = [1] * like.ndim
    shape[axis] = mask.shape[0]
    return mask.reshape(shape)


# ---------------------------------------------------------------------------
# shared backend skeleton
# ---------------------------------------------------------------------------

class _Backend:
    name = "?"

    def __init__(self, cfg: ArchConfig, n_slots: int, max_len: int):
        self.cfg = cfg
        self.n_slots = n_slots
        self.max_len = max_len
        self._decode = jax.jit(self._decode_impl, donate_argnums=(1,))
        self._write = jax.jit(self._write_impl, donate_argnums=(0,))
        self._reset = jax.jit(self._reset_impl, donate_argnums=(0,))
        self.routed = ()

    # -- public jitted entry points ------------------------------------
    def decode(self, params, caches, tables, tokens, positions):
        nxt, logits, caches, self.routed = self._decode(
            params, caches, tables, tokens, positions)
        return nxt, logits, caches

    def write_prefill(self, caches, contribs, slot_ids, lengths, tables):
        return self._write(caches, contribs, slot_ids, lengths, tables)

    def reset_slots(self, caches, slot_mask):
        return self._reset(caches, slot_mask)

    # -- recurrent-state helpers shared by both backends ---------------
    def _write_states(self, kind, stacked, cache, contrib, slot_ids):
        """Scatter final recurrent states into slot rows (sentinel row
        ids are dropped — padded prefill rows)."""
        def put(leaf, new):
            if stacked:
                return leaf.at[:, slot_ids].set(new.astype(leaf.dtype),
                                                mode="drop")
            return leaf.at[slot_ids].set(new.astype(leaf.dtype),
                                         mode="drop")

        return jax.tree_util.tree_map(put, cache, contrib)

    def _reset_states(self, kind, stacked, cache, slot_mask):
        fresh_one = tf._init_layer_cache(kind, self.cfg, self.n_slots,
                                         self.max_len, self._cache_dtype())
        axis = 1 if stacked else 0

        def mix(leaf, fresh):
            if stacked:
                fresh = jnp.broadcast_to(fresh, leaf.shape)
            m = _mask_rows(slot_mask, leaf, axis)
            return jnp.where(m, fresh.astype(leaf.dtype), leaf)

        return jax.tree_util.tree_map(mix, cache, fresh_one)

    def _cache_dtype(self):
        from repro.models.lm import layers as ll
        return ll.cdtype(self.cfg)

    # -- impls ---------------------------------------------------------
    def _decode_impl(self, params, caches, tables, tokens, positions):
        raise NotImplementedError

    def _write_impl(self, caches, contribs, slot_ids, lengths, tables):
        raise NotImplementedError

    def _reset_impl(self, caches, slot_mask):
        def one(kind, stacked, cache, _):
            if kind in ("global", "local"):
                return cache  # stale KV is masked, never read
            return self._reset_states(kind, stacked, cache, slot_mask)

        return map_layer_caches(caches, None, self.cfg, one)


class DenseBackend(_Backend):
    """Per-slot ring caches — the legacy serve_step layout, upgraded to
    per-slot position vectors. Serves as the bit-exact reference the
    paged backend is tested against."""

    name = "dense"

    def init_caches(self):
        return tf.init_caches(self.cfg, self.n_slots, self.max_len)

    def _decode_impl(self, params, caches, tables, tokens, positions):
        del tables
        logits, caches, routed = tf.decode_step(
            steps_lib.cast_compute(params, self.cfg), tokens, positions,
            caches, self.cfg, with_routed=True)
        return (jnp.argmax(logits, -1).astype(jnp.int32), logits, caches,
                routed)

    def _write_impl(self, caches, contribs, slot_ids, lengths, tables):
        del tables

        def one(kind, stacked, cache, contrib):
            if kind not in ("global", "local"):
                return self._write_states(kind, stacked, cache, contrib,
                                          slot_ids)
            k_new, v_new = contrib

            def write(ring, kv):
                ring_len = ring.shape[2] if stacked else ring.shape[1]
                def put(ring1, kv1):
                    vals, valid = _ring_vals(kv1, lengths, ring_len)
                    rows = jnp.clip(slot_ids, 0, ring1.shape[0] - 1)
                    old = ring1[rows]
                    keep = valid.reshape(valid.shape + (1, 1))
                    new = jnp.where(keep, vals.astype(ring1.dtype), old)
                    return ring1.at[slot_ids].set(new, mode="drop")
                if stacked:
                    return jax.vmap(put)(ring, kv)
                return put(ring, kv)

            return attn.KVCache(write(cache.k, k_new), write(cache.v, v_new))

        return map_layer_caches(caches, contribs, self.cfg, one)


class PagedBackend(_Backend):
    """Block-table-indexed KV pools + per-slot recurrent-state rows."""

    name = "paged"

    def __init__(self, cfg: ArchConfig, n_slots: int, max_len: int,
                 block_size: int, n_blocks: Optional[Dict[str, int]] = None,
                 spec_tokens: int = 0):
        kinds = [k for k in ("global", "local")
                 if k in set(cfg.pattern_for_layers)]
        self.block_size = block_size
        self.spec_tokens = spec_tokens
        if spec_tokens:
            # Speculative drafting appends Q = spec_tokens + 1 tokens per
            # step, which needs ring headroom for sequential-exactness:
            #   * local rings get window + spec_tokens entries so no write
            #     can land inside an earlier draft token's window
            #     (attention_decode_paged's no-wrap condition);
            #   * global rings must hold positions up to
            #     max_len - 1 + spec_tokens (the last step for a slot may
            #     draft past its final committed token) — otherwise the
            #     clip at ring_len - 1 would scatter two draft tokens to
            #     ONE entry, an unspecified-winner collision.
            # Rounded up to block granularity; the extra entries are
            # mask-invalid, so they change capacity, never output.
            # min(window + K, max_len + K) == min(window, max_len) + K and
            # rounding only grows the ring, so the headroom bound holds by
            # construction for every (window, max_len, K) — the only
            # runtime fail-fast left is attention_decode_paged's
            # q_len > ring_len collision guard.
            alloc = max_len + spec_tokens
            self.ring_len = {
                k: -(-attn.cache_len(cfg, k, alloc, headroom=spec_tokens)
                     // block_size) * block_size
                for k in kinds}
        else:
            self.ring_len = {k: attn.cache_len(cfg, k, max_len)
                             for k in kinds}
            for k, l in self.ring_len.items():
                if l % block_size != 0:
                    raise ValueError(
                        f"block_size={block_size} must divide the {k!r} ring "
                        f"length {l} (max_len={max_len}, "
                        f"local_window={cfg.local_window})")
        self.blocks_per_slot = {k: l // block_size
                                for k, l in self.ring_len.items()}
        self.n_blocks = dict(n_blocks) if n_blocks else {
            k: n_slots * nb for k, nb in self.blocks_per_slot.items()}
        for k, nb in self.blocks_per_slot.items():
            if self.n_blocks.get(k, 0) < nb:
                raise ValueError(
                    f"n_blocks[{k!r}]={self.n_blocks.get(k)} cannot cover "
                    f"even one slot ({nb} blocks/slot) — no request could "
                    f"ever be admitted")
        super().__init__(cfg, n_slots, max_len)
        if spec_tokens:
            self._decode_spec = jax.jit(self._decode_spec_impl,
                                        donate_argnums=(1,))

    def init_caches(self):
        return tf.init_paged_caches(self.cfg, self.n_slots, self.block_size,
                                    self.n_blocks, self.max_len)

    def covered_blocks(self, max_pos: int) -> Dict[str, int]:
        """Per-kind count of table blocks that can hold any entry a slot
        at position <= max_pos could have written: ring slots only ever
        reach min(max_pos + 1, ring_len), so blocks past that prefix are
        provably dead — the engine slices them off the device tables and
        the decode program (fused kernel AND gather fallback) never
        touches them. Bucketed to powers of two to bound retraces."""
        need = max(1, max_pos + 1)
        out = {}
        for kind, nb in self.blocks_per_slot.items():
            k = -(-min(need, self.ring_len[kind]) // self.block_size)
            b = 1
            while b < k:
                b *= 2
            out[kind] = min(b, nb)
        return out

    def _decode_impl(self, params, caches, tables, tokens, positions):
        logits, caches, routed = tf.decode_step_paged(
            steps_lib.cast_compute(params, self.cfg), tokens, positions,
            caches, tables, self.cfg, ring_lens=self.ring_len,
            with_routed=True)
        return (jnp.argmax(logits, -1).astype(jnp.int32), logits, caches,
                routed)

    # -- speculative draft/verify ---------------------------------------
    def decode_spec(self, params, caches, tables, tokens, positions):
        """One draft/verify step. tokens [B, Q]: column 0 = last committed
        token, 1..Q-1 = drafts. Returns (greedy [B, Q], logits [B, Q, V],
        keep [B], caches): greedy[:, t] is the token greedy decode emits
        after accepting tokens 0..t; keep in 1..Q is how many input tokens
        stand (1 committed + accepted drafts) — the engine commits
        greedy[:, :keep] and advances positions by keep. Recurrent-layer
        states are already rolled back to the keep'th token in-trace; KV
        entries of rejected drafts need no rollback (the next append
        rewrites them before any read — decode_step_spec docstring)."""
        if not self.spec_tokens:
            raise ValueError("backend built without spec_tokens")
        return self._decode_spec(params, caches, tables, tokens, positions)

    def _decode_spec_impl(self, params, caches, tables, tokens, positions):
        logits, caches = tf.decode_step_spec(
            steps_lib.cast_compute(params, self.cfg), tokens, positions,
            caches, tables, self.cfg, ring_lens=self.ring_len)
        greedy = jnp.argmax(logits, -1).astype(jnp.int32)       # [B, Q]
        # longest matching prefix: draft t (= tokens[:, t+1]) is accepted
        # iff every draft before it was AND it equals the target's greedy
        # continuation greedy[:, t]
        match = (tokens[:, 1:] == greedy[:, :-1]).astype(jnp.int32)
        keep = 1 + jnp.cumprod(match, axis=1).sum(axis=1).astype(jnp.int32)
        caches = self._select_spec_states(caches, keep)
        return greedy, logits, keep, caches

    def _select_spec_states(self, caches, keep):
        """Roll recurrent-layer states back to the last kept token: the
        spec driver stacks them per token ([Q, ...]; [reps, Q, ...] in
        scanned units) and this picks index keep - 1 per slot. Attention
        pools pass through untouched (their stale entries self-heal)."""
        km1 = keep - 1
        rows = jnp.arange(keep.shape[0])

        def one(kind, stacked, cache, _):
            if kind in ("global", "local"):
                return cache

            def sel(leaf):
                return leaf[:, km1, rows] if stacked else leaf[km1, rows]

            return jax.tree_util.tree_map(sel, cache)

        return map_layer_caches(caches, None, self.cfg, one)

    def _write_impl(self, caches, contribs, slot_ids, lengths, tables):
        bs = self.block_size

        def one(kind, stacked, cache, contrib):
            if kind not in ("global", "local"):
                return self._write_states(kind, stacked, cache, contrib,
                                          slot_ids)
            k_new, v_new = contrib
            table = tables[kind]                       # [n_slots, nb]
            ring_len = table.shape[1] * bs
            rows = jnp.clip(slot_ids, 0, table.shape[0] - 1)
            phys = jnp.repeat(table[rows], bs, axis=1)  # [Bp, ring_len]
            active = (slot_ids < table.shape[0])[:, None]
            off = jnp.broadcast_to(
                (jnp.arange(ring_len) % bs)[None, :], phys.shape)

            def write(pool, kv):
                def put(pool1, kv1):
                    vals, valid = _ring_vals(kv1, lengths, ring_len)
                    ok = valid & active & (phys >= 0)
                    phys_w = jnp.where(ok, phys, pool1.shape[0])
                    return pool1.at[phys_w, off].set(
                        vals.astype(pool1.dtype), mode="drop")
                if stacked:
                    return jax.vmap(put)(pool, kv)
                return put(pool, kv)

            return attn.PagedKV(write(cache.k, k_new), write(cache.v, v_new))

        return map_layer_caches(caches, contribs, self.cfg, one)


def make_backend(name: str, cfg: ArchConfig, n_slots: int, max_len: int,
                 block_size: int,
                 n_blocks: Optional[Dict[str, int]] = None,
                 spec_tokens: int = 0) -> _Backend:
    if name == "dense":
        if spec_tokens:
            raise ValueError(
                "speculative decoding needs the paged backend (the dense "
                "ring writer is single-token)")
        return DenseBackend(cfg, n_slots, max_len)
    if name == "paged":
        return PagedBackend(cfg, n_slots, max_len, block_size, n_blocks,
                            spec_tokens)
    raise ValueError(f"unknown cache backend {name!r}")
