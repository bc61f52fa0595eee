"""The LM stack: embedding -> pattern-cycled blocks -> norm -> head.

Layer layout: cfg.pattern (e.g. 5x local + 1 global for gemma3; 2x rglru +
local for recurrentgemma; 7x mlstm + slstm for xlstm) defines a repeating
UNIT. Parameters for each pattern position are stacked over the R unit
repeats and the stack runs as ONE jax.lax.scan over R — the traced HLO holds
a single unit regardless of depth (56-layer mixtral compiles as fast as a
2-layer smoke config). Remainder layers (n_layers % len(pattern)), and a
stack of a single repeat, are traced inline. A stack whose kinds are listed per layer (cfg.layer_kinds) scans as
many whole repeats of the pattern as its kinds begin with, after its
leading dense layers (cfg.n_dense_layers, inline under 'lead'), and runs
the rest inline.

Train path returns fp32 logits (+ MoE aux loss); decode path threads
per-layer caches (KV / recurrent states) through the same scan.

Serving (PR 3): `decode_step` takes per-slot position vectors, and the
paged twins (`init_paged_caches` / `decode_step_paged`) run the same stack
against block-table-indexed KV pools — the substrate of the continuous-
batching engine in repro.serve. `forward_prefill` is the batched prefill:
one full-sequence forward that also returns every layer's cache
contribution (rope'd K/V for attention, final recurrent states at each
slot's own prompt length) for scatter-insertion into either cache layout.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.parallel import act_sharding as sa
from repro.models.lm import attention as attn
from repro.models.lm import ffn as ffn_lib
from repro.models.lm import layers as ll
from repro.models.lm import moe as moe_lib
from repro.models.lm import rglru as rglru_lib
from repro.models.lm import shortconv as conv_lib
from repro.models.lm import xlstm as xlstm_lib

Array = jnp.ndarray
Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# per-kind layer init / apply
# ---------------------------------------------------------------------------

def _layer_init(key, kind: str, cfg: ArchConfig, moe: bool = True
                ) -> Params:
    """`moe` False gives the layer the dense FFN where the config has
    experts (the leading dense layers)."""
    if kind in ("global", "local", "conv"):
        k1, k2 = jax.random.split(key)
        p = {"ln1": ll.rmsnorm_init(cfg.d_model),
             "ln2": ll.rmsnorm_init(cfg.d_model)}
        if kind == "conv":
            p["shortconv"] = conv_lib.shortconv_init(k1, cfg)
        else:
            p["attn"] = attn.attn_init(k1, cfg)
        if moe and cfg.moe.n_experts > 0:
            p["moe"] = moe_lib.moe_init(k2, cfg)
        elif cfg.ffn_type != "none":
            p["ffn"] = ffn_lib.ffn_init(k2, cfg)
        return p
    if kind == "mlstm":
        return {"block": xlstm_lib.mlstm_init(key, cfg)}
    if kind == "slstm":
        return {"block": xlstm_lib.slstm_init(key, cfg)}
    if kind == "rglru":
        k1, k2 = jax.random.split(key)
        return {
            "ln1": ll.rmsnorm_init(cfg.d_model),
            "rec": rglru_lib.rglru_init(k1, cfg),
            "ln2": ll.rmsnorm_init(cfg.d_model),
            "ffn": ffn_lib.ffn_init(k2, cfg),
        }
    raise ValueError(f"unknown layer kind {kind!r}")


def _seq_shard(x: Array, cfg: ArchConfig) -> Array:
    """Megatron-SP (§Perf iter 4): residual stream [B,S,d] seq-sharded over
    'model' between blocks; GSPMD's ar+slice->reduce-scatter rewrite turns
    the row-parallel ARs into RS and inserts AGs at the matmul boundaries."""
    return sa.shard_act(x, sa.U, "model", sa.U,
                        enabled=cfg.act_sharding and cfg.seq_sharding)


def _layer_train(p: Params, x: Array, kind: str, cfg: ArchConfig,
                 positions: Array) -> Tuple[Array, Array]:
    """returns (x, aux_loss)."""
    x = _seq_shard(x, cfg)
    aux = jnp.zeros((), jnp.float32)
    if kind in ("global", "local", "conv"):
        h = ll.rmsnorm_apply(p["ln1"], x, cfg.norm_eps)
        if kind == "conv":
            x = x + conv_lib.shortconv_apply(p["shortconv"], h, cfg)
        else:
            x = x + attn.attention_train(p["attn"], h, cfg, kind=kind,
                                         positions=positions)
        h = ll.rmsnorm_apply(p["ln2"], x, cfg.norm_eps)
        if "moe" in p:
            y, aux, _ = moe_lib.moe_apply(p["moe"], h, cfg)
            x = x + y
        elif "ffn" in p:
            x = x + ffn_lib.ffn_apply(p["ffn"], h, cfg)
        return x, aux
    if kind == "mlstm":
        return x + xlstm_lib.mlstm_apply(p["block"], x, cfg), aux
    if kind == "slstm":
        return x + xlstm_lib.slstm_apply(p["block"], x, cfg), aux
    if kind == "rglru":
        h = ll.rmsnorm_apply(p["ln1"], x, cfg.norm_eps)
        x = x + rglru_lib.rglru_apply(p["rec"], h, cfg)
        h = ll.rmsnorm_apply(p["ln2"], x, cfg.norm_eps)
        x = x + ffn_lib.ffn_apply(p["ffn"], h, cfg)
        return x, aux
    raise ValueError(kind)


def _attn_residual(p: Params, x: Array, cfg: ArchConfig, attn_fn,
                   valid=None):
    """The mixer residual block shared by the decode/paged-decode/
    prefill paths: ln1 -> attn_fn -> residual -> ln2 -> moe/ffn.
    attn_fn(h) -> (y, extra); `extra` is the cache / KV contribution.
    ONE implementation on purpose — the CI-gated paged/dense parity
    invariant assumes these paths cannot drift apart. Returns (x, extra,
    routing (`moe.moe_apply`), or () without experts); `valid` [B, S]
    marks real tokens for the expert dispatch."""
    h = ll.rmsnorm_apply(p["ln1"], x, cfg.norm_eps)
    y, extra = attn_fn(h)
    x = x + y
    h = ll.rmsnorm_apply(p["ln2"], x, cfg.norm_eps)
    routed = ()
    if "moe" in p:
        y, _, routed = moe_lib.moe_apply(p["moe"], h, cfg, valid)
        x = x + y
    elif "ffn" in p:
        x = x + ffn_lib.ffn_apply(p["ffn"], h, cfg)
    return x, extra, routed


def _routed_sum(parts):
    """One multi-token append's routing out of its per-token parts: the
    rows summed, the tokens' selections in [B, Q] row order."""
    if isinstance(parts[0], tuple):
        return ()
    top = jnp.stack([r["top"] for r in parts], axis=1)
    return {"rows": sum(r["rows"] for r in parts),
            "top": top.reshape(-1, top.shape[-1])}


def _routed_rows(lead, units, tail):
    """The routing of each MoE layer in layer order, or () in a model
    without experts: 'rows' [n_moe_layers, E_here] int32, the rows routed
    to each held expert, and 'top' [n_moe_layers, T, k] int32, each
    token's selected experts. `units` holds each pattern position's stack
    over the scan (leading axis reps)."""
    tmap = jax.tree_util.tree_map
    parts = [tmap(lambda a: a[None], r) for r in lead if isinstance(r, dict)]
    stacked = [r for r in units if isinstance(r, dict)]
    if stacked:
        parts.append(tmap(lambda *a: jnp.stack(a, axis=1).reshape(
            -1, *a[0].shape[1:]), *stacked))
    parts += [tmap(lambda a: a[None], r) for r in tail if isinstance(r, dict)]
    return tmap(lambda *a: jnp.concatenate(a), *parts) if parts else ()


def _recurrent_decode_multi(p: Params, x: Array, kind: str, cfg: ArchConfig,
                            position: Array, cache):
    """Multi-token append for the recurrent cells (speculative-decode
    drafts on the hybrid stacks): scan the SAME one-token decode cell over
    the Q tokens — each step sees exactly the [B, 1, d] shapes of ordinary
    decode, so the outputs are bitwise identical to Q sequential steps —
    and keep EVERY per-token state. Unlike KV rings (where a rejected
    draft's entries are overwritten by the next append before anything
    reads them), recurrent state folds each token in irreversibly, so
    verification must roll back to the state of the last ACCEPTED token:
    the returned states carry a leading per-token axis [Q, ...] and the
    caller (backends.PagedBackend) selects index `accepted` per slot."""
    ys, states, routed = [], [], []
    state = cache
    for t in range(x.shape[1]):  # static Q, small — unrolled on purpose:
        # a lax.scan body is compiled once and may fuse differently from
        # the single-token step the bit-parity gate compares against
        y, state, r = _layer_decode(p, x[:, t : t + 1], kind, cfg, position,
                                    state)
        ys.append(y[:, 0])
        states.append(state)
        routed.append(r)
    stacked = jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves, axis=0), *states)
    return jnp.stack(ys, axis=1), stacked, _routed_sum(routed)


def _layer_decode(p: Params, x: Array, kind: str, cfg: ArchConfig,
                  position: Array, cache, block_tables=None,
                  ring_lens=None):
    """block_tables None -> dense ring cache; a per-kind table dict ->
    paged pools (attention kinds only; recurrent caches are identical
    in both layouts). ring_lens carries the true per-kind ring geometry
    when the tables are covered-prefix slices (dead-block skipping).

    x [B, Q, d] with Q > 1 is the multi-token (speculative verify) step:
    attention kinds batch all Q tokens through one paged append; recurrent
    kinds scan the one-token cell and return per-token states stacked
    [Q, ...] (see _recurrent_decode_multi). Returns (x, cache, the MoE
    routing or ())."""
    if kind not in ("global", "local") and x.shape[1] > 1:
        return _recurrent_decode_multi(p, x, kind, cfg, position, cache)
    if kind in ("global", "local"):
        if block_tables is None:
            return _attn_residual(p, x, cfg, lambda h: attn.attention_decode(
                p["attn"], h, cfg, kind=kind, position=position, cache=cache))
        return _attn_residual(p, x, cfg, lambda h: attn.attention_decode_paged(
            p["attn"], h, cfg, kind=kind, position=position, cache=cache,
            block_table=block_tables[kind],
            ring_len=ring_lens[kind] if ring_lens else None))
    if kind == "conv":
        return _attn_residual(p, x, cfg, lambda h: conv_lib.shortconv_decode(
            p["shortconv"], h, cfg, cache))
    if kind == "mlstm":
        y, cache = xlstm_lib.mlstm_decode(p["block"], x, cfg, cache)
        return x + y, cache, ()
    if kind == "slstm":
        y, cache = xlstm_lib.slstm_decode(p["block"], x, cfg, cache)
        return x + y, cache, ()
    if kind == "rglru":
        h = ll.rmsnorm_apply(p["ln1"], x, cfg.norm_eps)
        y, cache = rglru_lib.rglru_decode(p["rec"], h, cfg, cache)
        x = x + y
        h = ll.rmsnorm_apply(p["ln2"], x, cfg.norm_eps)
        x = x + ffn_lib.ffn_apply(p["ffn"], h, cfg)
        return x, cache, ()
    raise ValueError(kind)


def _init_layer_cache(kind: str, cfg: ArchConfig, batch: int, seq_len: int,
                      dtype):
    if kind in ("global", "local"):
        return attn.init_cache(cfg, kind, batch, seq_len, dtype)
    if kind == "mlstm":
        return xlstm_lib.mlstm_init_state(cfg, batch)
    if kind == "slstm":
        return xlstm_lib.slstm_init_state(cfg, batch)
    if kind == "rglru":
        return rglru_lib.rglru_init_state(cfg, batch)
    if kind == "conv":
        return conv_lib.shortconv_init_state(cfg, batch)
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# stack layout
# ---------------------------------------------------------------------------

def _layout(cfg: ArchConfig) -> Tuple[int, Tuple[str, ...], Tuple[str, ...]]:
    """After the leading dense layers: as many whole repeats of `pattern`
    as the layer kinds begin with (scanned where there are two or more: a
    scan over one repeat saves no compile time and carries its caches
    through the loop), then the rest inline."""
    kinds = cfg.pattern_for_layers[cfg.n_dense_layers:]
    p = len(cfg.pattern)
    reps = 0
    while cfg.scan_layers and kinds[reps * p:(reps + 1) * p] == cfg.pattern:
        reps += 1
    if reps < 2:
        reps = 0
    return reps, cfg.pattern, kinds[reps * p :]


def layout(cfg: ArchConfig) -> Tuple[int, Tuple[str, ...], Tuple[str, ...]]:
    """Public stack layout: (scan_reps, pattern, tail_kinds). The caches
    pytree mirrors it: caches['units'][j] is pattern position j stacked
    over reps; caches['tail'][i] belongs to tail kind i. Leading dense
    layers (`lead_kinds`) come first, under 'lead', where there are any."""
    return _layout(cfg)


def lead_kinds(cfg: ArchConfig) -> Tuple[str, ...]:
    """Kinds of the leading dense layers, inline before the scan."""
    return cfg.pattern_for_layers[: cfg.n_dense_layers]


def init(key, cfg: ArchConfig) -> Params:
    reps, pattern, tail = _layout(cfg)
    keys = jax.random.split(key, 4)
    params: Params = {
        "embed": ll.embedding_init(keys[0], cfg.padded_vocab, cfg.d_model),
        "final_norm": ll.rmsnorm_init(cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["head"] = ll.linear_init(keys[1], cfg.d_model, cfg.padded_vocab,
                                        cfg)
    if cfg.frontend is not None:
        params["frontend_proj"] = ll.linear_init(
            keys[2], cfg.frontend_dim, cfg.d_model, cfg, bias=True
        )

    lead = lead_kinds(cfg)
    if lead:
        params["lead"] = tuple(
            _layer_init(k, kind, cfg, moe=False)
            for k, kind in zip(jax.random.split(jax.random.fold_in(key, 4),
                                                len(lead)), lead))
    lkeys = jax.random.split(keys[3], max(reps, 1) * len(pattern) + len(tail))
    if reps > 0:
        units = []
        for j, kind in enumerate(pattern):
            ks = jnp.stack([lkeys[r * len(pattern) + j] for r in range(reps)])
            units.append(jax.vmap(lambda k: _layer_init(k, kind, cfg))(ks))
        params["units"] = tuple(units)
    params["tail"] = tuple(
        _layer_init(lkeys[reps * len(pattern) + i], kind, cfg)
        for i, kind in enumerate(tail)
    )
    return params


# ---------------------------------------------------------------------------
# embedding frontends
# ---------------------------------------------------------------------------

def _embed_inputs(params: Params, batch: Dict[str, Array], cfg: ArchConfig) -> Array:
    if cfg.frontend == "audio":
        return ll.linear_apply(params["frontend_proj"], batch["frames"], cfg)
    x = ll.embed(params["embed"], batch["tokens"], cfg)
    if cfg.frontend == "vit":
        patches = ll.linear_apply(params["frontend_proj"], batch["patches"], cfg)
        x = jnp.concatenate([patches.astype(x.dtype), x[:, patches.shape[1]:]],
                            axis=1)
    return x


# ---------------------------------------------------------------------------
# train forward
# ---------------------------------------------------------------------------

def forward_train(params: Params, batch: Dict[str, Array],
                  cfg: ArchConfig) -> Tuple[Array, Array]:
    """batch: {'tokens': [B,S]} (+ 'patches'/'frames' per frontend).
    Returns (logits fp32 [B,S,V], aux_loss)."""
    reps, pattern, tail = _layout(cfg)
    x = _embed_inputs(params, batch, cfg)
    positions = jnp.arange(x.shape[1])[None, :]

    def unit_body(carry, unit_params):
        x, aux = carry
        for j, kind in enumerate(pattern):
            x, a = _layer_train(unit_params[j], x, kind, cfg, positions)
            aux = aux + a
        return (x, aux), None

    if cfg.remat:
        unit_body = jax.checkpoint(unit_body, prevent_cse=False)

    aux = jnp.zeros((), jnp.float32)
    for i, kind in enumerate(lead_kinds(cfg)):
        x, _ = _layer_train(params["lead"][i], x, kind, cfg, positions)
    if reps > 0:
        (x, aux), _ = jax.lax.scan(unit_body, (x, aux), params["units"])
    for i, kind in enumerate(tail):
        x, a = _layer_train(params["tail"][i], x, kind, cfg, positions)
        aux = aux + a

    x = ll.rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
    logits = ll.lm_head(params.get("head"), params["embed"], x, cfg)
    return logits, aux


def lm_loss(logits: Array, labels: Array, *, z_loss: float = 1e-4
            ) -> Tuple[Array, Dict[str, Array]]:
    """Causal LM CE (+ z-loss). labels [B, S] int32; -1 = masked."""
    mask = (labels >= 0).astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll_ = jnp.take_along_axis(logits, jnp.maximum(labels, 0)[..., None],
                              axis=-1)[..., 0]
    ce = (lse - ll_) * mask
    zl = z_loss * jnp.square(lse) * mask
    denom = jnp.maximum(mask.sum(), 1.0)
    loss = (ce + zl).sum() / denom
    return loss, {"ce": ce.sum() / denom,
                  "acc": ((jnp.argmax(logits, -1) == labels) * mask).sum() / denom}


# ---------------------------------------------------------------------------
# decode (serve) path
# ---------------------------------------------------------------------------

def init_caches(cfg: ArchConfig, batch: int, seq_len: int, dtype=None):
    """Stacked caches per pattern position (+ per tail layer)."""
    dtype = dtype or ll.cdtype(cfg)
    reps, pattern, tail = _layout(cfg)

    def stack(kind):
        one = _init_layer_cache(kind, cfg, batch, seq_len, dtype)
        return jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a, (reps, *a.shape)).copy(), one
        )

    units = tuple(stack(kind) for kind in pattern) if reps > 0 else ()
    tails = tuple(
        _init_layer_cache(kind, cfg, batch, seq_len, dtype) for kind in tail
    )
    return _with_lead({"units": units, "tail": tails}, tuple(
        _init_layer_cache(kind, cfg, batch, seq_len, dtype)
        for kind in lead_kinds(cfg)))


def _with_lead(tree: Dict, lead: Tuple) -> Dict:
    """A caches/contribs pytree with the leading layers' part under
    'lead', where the model has leading layers."""
    return dict(tree, lead=lead) if lead else tree


def _decode_driver(params: Params, tokens: Array, position: Array, caches,
                   cfg: ArchConfig, block_tables,
                   ring_lens=None) -> Tuple[Array, Any, Any]:
    """tokens [B] -> (logits [B, V], caches, routed); tokens [B, Q]
    (multi-token append) -> (logits [B, Q, V], caches with recurrent-layer
    states stacked per token, routed). `routed`: `_routed_rows`."""
    reps, pattern, tail = _layout(cfg)
    multi = tokens.ndim == 2
    x = ll.embed(params["embed"], tokens if multi else tokens[:, None], cfg)

    new_lead, lead_routed = [], []
    for i, kind in enumerate(lead_kinds(cfg)):
        with ll.tap_scope(f"lead{i:02d}.{kind}"):
            x, c, r = _layer_decode(params["lead"][i], x, kind, cfg,
                                    position, caches["lead"][i],
                                    block_tables, ring_lens)
        new_lead.append(c)
        lead_routed.append(r)

    def unit_body(x, scanned):
        unit_params, unit_caches = scanned
        new_caches, routed = [], []
        for j, kind in enumerate(pattern):
            x, c, r = _layer_decode(unit_params[j], x, kind, cfg, position,
                                    unit_caches[j], block_tables, ring_lens)
            new_caches.append(c)
            routed.append(r)
        return x, (tuple(new_caches), tuple(routed))

    if reps > 0:
        x, (new_unit_caches, unit_routed) = jax.lax.scan(
            unit_body, x, (params["units"], caches["units"])
        )
    else:
        new_unit_caches, unit_routed = (), ()

    new_tail, tail_routed = [], []
    for i, kind in enumerate(tail):
        with ll.tap_scope(f"tail{i:02d}.{kind}"):
            x, c, r = _layer_decode(params["tail"][i], x, kind, cfg,
                                    position, caches["tail"][i],
                                    block_tables, ring_lens)
        new_tail.append(c)
        tail_routed.append(r)

    x = ll.rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
    logits = ll.lm_head(params.get("head"), params["embed"], x, cfg)
    new_caches = _with_lead({"units": new_unit_caches,
                             "tail": tuple(new_tail)}, tuple(new_lead))
    return (logits if multi else logits[:, 0]), new_caches, _routed_rows(
        lead_routed, unit_routed, tail_routed)


def decode_step(params: Params, tokens: Array, position: Array, caches,
                cfg: ArchConfig, *, with_routed: bool = False):
    """One decode step: tokens [B] int32 -> logits [B, V], new caches.
    position: scalar int32 (whole batch at one index) or [B] vector
    (continuous batching: every cache slot at its own offset).
    `with_routed` adds each MoE layer's routing (`_routed_rows`)."""
    out = _decode_driver(params, tokens, position, caches, cfg, None)
    return out if with_routed else out[:2]


def decode_step_paged(params: Params, tokens: Array, position: Array, caches,
                      block_tables: Dict[str, Array], cfg: ArchConfig,
                      ring_lens: Optional[Dict[str, int]] = None, *,
                      with_routed: bool = False):
    """decode_step against paged KV pools. block_tables: one [B, nb] int32
    table per attention kind present in the pattern (shared by every layer
    of that kind; -1 marks unallocated blocks). The tables may be COVERED-
    PREFIX slices of the full tables (the serve engine drops blocks no
    slot position can reach — dead blocks cost nothing even on the XLA
    gather path); `ring_lens` then carries the true per-kind ring lengths.
    On the "xla" paged_attn_impl path the logits are bit-identical to
    decode_step when the pools hold the same entries the dense ring does;
    the fused kernel path is allclose-parity-gated against it."""
    out = _decode_driver(params, tokens, position, caches, cfg, block_tables,
                         ring_lens)
    return out if with_routed else out[:2]


def decode_step_spec(params: Params, tokens: Array, position: Array, caches,
                     block_tables: Dict[str, Array], cfg: ArchConfig,
                     ring_lens: Optional[Dict[str, int]] = None
                     ) -> Tuple[Array, Any]:
    """Speculative verify step: score Q tokens per slot in ONE forward.

    tokens [B, Q] int32 — column 0 is the last committed token, columns
    1..Q-1 the draft proposals; position [B] is the base position of
    column 0 (token t sits at position + t). Returns (logits [B, Q, V],
    caches): logits[:, t] is conditioned on the prefix ending at token t,
    so argmax(logits[:, t]) is the token greedy decode would emit after
    accepting tokens 0..t — the verification signal.

    Cache semantics under partial acceptance:
      * attention (paged KV): all Q tokens' K/V are written (the multi-
        token append of attention_decode_paged). Rejected-draft entries
        need NO rollback — the next append's base advances by the commit
        count c >= 1 and spans [base+c, base+c+Q-1] ⊇ the stale region
        [base+c, base+Q-1], so every stale entry is rewritten before any
        q-token can attend it (appends write first, attend second). The
        bit-exactness of this path additionally needs ring headroom on
        local layers — see attention_decode_paged / cache_len(headroom=).
      * recurrent layers: state folds tokens in irreversibly, so the
        returned caches carry per-token states stacked [Q, ...]; the
        caller must select the accepted token's state (and MUST NOT feed
        these stacked caches back into a Q == 1 step unselected).
    """
    if tokens.ndim != 2 or tokens.shape[1] < 2:
        raise ValueError(
            f"decode_step_spec wants tokens [B, Q >= 2]; got "
            f"{tokens.shape} (use decode_step_paged for single tokens)")
    return _decode_driver(params, tokens, position, caches, cfg, block_tables,
                          ring_lens)[:2]


# ---------------------------------------------------------------------------
# paged cache init (block-table KV pools; repro.serve drives this)
# ---------------------------------------------------------------------------

def init_paged_caches(cfg: ArchConfig, n_slots: int, block_size: int,
                      n_blocks: Dict[str, int], max_len: int, dtype=None):
    """Paged mirror of init_caches. Attention layers hold PagedKV pools
    ([reps?, n_blocks[kind], block_size, K, hd]); recurrent layers keep
    per-slot state rows exactly as the dense layout (batch == n_slots).
    Every layer of one attention kind shares the engine's single block
    table for that kind (vLLM-style: one table, all layers)."""
    dtype = dtype or ll.cdtype(cfg)
    reps, pattern, tail = _layout(cfg)

    def one(kind):
        if kind in ("global", "local"):
            return attn.init_paged_pool(cfg, n_blocks[kind], block_size,
                                        dtype)
        return _init_layer_cache(kind, cfg, n_slots, max_len, dtype)

    def stack(kind):
        return jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a, (reps, *a.shape)).copy(), one(kind)
        )

    units = tuple(stack(kind) for kind in pattern) if reps > 0 else ()
    tails = tuple(one(kind) for kind in tail)
    return _with_lead({"units": units, "tail": tails},
                      tuple(one(kind) for kind in lead_kinds(cfg)))


# ---------------------------------------------------------------------------
# batched prefill (full-sequence forward that yields cache contributions)
# ---------------------------------------------------------------------------

def _layer_prefill(p: Params, x: Array, kind: str, cfg: ArchConfig,
                   positions: Array, lengths: Array, valid=None):
    """Returns (x, contrib, routed): contrib is (k, v) [B, S, K, hd] for
    attention layers, the final per-slot recurrent state otherwise. The
    gated short conv runs the whole wave in parallel and takes each
    slot's state at its own length. The other recurrent kinds scan the
    DECODE cell over time (state updates frozen at t >= length — ragged
    prompts), which makes their prefill state bit-identical to feeding
    the prompt through the decode path token by token. `valid` [B, S]
    marks the real tokens, which alone reach the experts."""
    if kind in ("global", "local"):
        return _attn_residual(p, x, cfg, lambda h: attn.attention_prefill(
            p["attn"], h, cfg, kind=kind, positions=positions), valid)
    if kind == "conv":
        return _attn_residual(p, x, cfg, lambda h: conv_lib.shortconv_prefill(
            p["shortconv"], h, cfg, lengths), valid)

    b, s = x.shape[0], x.shape[1]
    state0 = _init_layer_cache(kind, cfg, b, s, ll.cdtype(cfg))

    def step(state, t):
        xt = jax.lax.dynamic_slice_in_dim(x, t, 1, axis=1)
        yt, new, _ = _layer_decode(p, xt, kind, cfg, t, state)
        keep = t < lengths  # [B] — freeze state past each slot's prompt
        new = jax.tree_util.tree_map(
            lambda nl, ol: jnp.where(
                keep.reshape((b,) + (1,) * (nl.ndim - 1)), nl, ol),
            new, state)
        return new, yt[:, 0]

    final, ys = jax.lax.scan(step, state0, jnp.arange(s))
    return jnp.moveaxis(ys, 0, 1), final, ()


def forward_prefill(params: Params, batch: Dict[str, Array], cfg: ArchConfig,
                    *, lengths: Optional[Array] = None,
                    with_routed: bool = False):
    """Batched prefill over left-aligned prompts (positions 0..S-1), with
    per-slot prompt lengths [B] (padded tail tokens contribute garbage the
    cache writers mask out, and are routed to no expert). Returns (logits
    fp32 [B, S, V], contribs) where contribs mirrors the init_caches
    structure; `with_routed` adds `_routed_rows`."""
    reps, pattern, tail = _layout(cfg)
    x = _embed_inputs(params, batch, cfg)
    b, s = x.shape[0], x.shape[1]
    valid = None
    if lengths is None:
        lengths = jnp.full((b,), s, jnp.int32)
    elif cfg.moe.n_experts > 0:
        valid = jnp.arange(s)[None, :] < lengths[:, None]
    positions = jnp.arange(s)[None, :]

    lead_contribs, lead_routed = [], []
    for i, kind in enumerate(lead_kinds(cfg)):
        x, c, r = _layer_prefill(params["lead"][i], x, kind, cfg, positions,
                                 lengths, valid)
        lead_contribs.append(c)
        lead_routed.append(r)

    def unit_body(x, unit_params):
        contribs, routed = [], []
        for j, kind in enumerate(pattern):
            x, c, r = _layer_prefill(unit_params[j], x, kind, cfg, positions,
                                     lengths, valid)
            contribs.append(c)
            routed.append(r)
        return x, (tuple(contribs), tuple(routed))

    if reps > 0:
        x, (unit_contribs, unit_routed) = jax.lax.scan(unit_body, x,
                                                       params["units"])
    else:
        unit_contribs, unit_routed = (), ()

    tail_contribs, tail_routed = [], []
    for i, kind in enumerate(tail):
        x, c, r = _layer_prefill(params["tail"][i], x, kind, cfg, positions,
                                 lengths, valid)
        tail_contribs.append(c)
        tail_routed.append(r)

    x = ll.rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
    logits = ll.lm_head(params.get("head"), params["embed"], x, cfg)
    contribs = _with_lead({"units": unit_contribs,
                           "tail": tuple(tail_contribs)},
                          tuple(lead_contribs))
    if not with_routed:
        return logits, contribs
    return logits, contribs, _routed_rows(lead_routed, unit_routed,
                                          tail_routed)


def unstack_tree(tree, cfg: ArchConfig):
    """Re-layout a scan-stacked params/caches pytree ({'units', 'tail'})
    for cfg.with_overrides(scan_layers=False): units[j][r] slices become
    inline tail entries in stack order. Used by the serve telemetry step,
    which must run unscanned so the per-layer psum tap can label layers."""
    reps, pattern, tail_kinds = _layout(cfg)
    units = tree.get("units", ())
    out_tail = []
    for r in range(reps):
        for j in range(len(pattern)):
            out_tail.append(jax.tree_util.tree_map(
                lambda a, r=r: a[r], units[j]))
    out_tail.extend(tree["tail"])
    out = dict(tree)
    out["units"] = ()
    out["tail"] = tuple(out_tail)
    return out


def param_count(params: Params) -> int:
    return sum(p.size for p in jax.tree_util.tree_leaves(params))
