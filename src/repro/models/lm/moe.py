"""Mixture-of-Experts: top-k routing with sort-based capacity dispatch.

Dispatch is the TPU-friendly sort formulation (no [T, E, C] one-hot):
  1. top-k expert ids per token -> flat (token, expert) pairs
  2. stable-sort pairs by expert
  3. position-within-expert via searchsorted; drop beyond capacity C
  4. scatter into a dense [E, C, d] buffer -> batched expert GEMMs
  5. gather back + weighted combine (scatter-add over tokens)

Under expert parallelism the [E, C, d] buffer is sharded on E over the
'model' axis; GSPMD lowers the scatter/gather to an all-to-all pair —
exactly the MoE dispatch collective a hand-written implementation would use.

Experts use SwiGLU; expert weights route through the CADC segmented layout
when cfg.linear_impl == 'cadc' (the paper's technique applies per expert
crossbar bank). Router stays fp32/dense (tiny, accuracy-critical).

A layer told which experts it holds (`MoEConfig.first_expert`, `n_held`)
routes over all of them and adds only its own experts' part: expert
parallelism's share, computed without the exchange. With
`capacity_factor` 0 nothing is dropped: the pairs routed here are sorted
by expert into whole row tiles and run through the grouped CADC matmul
(`kernels.ops.cadc_gmm`, the fused kernel on TPU); pairs routed elsewhere
add nothing. Every call also returns the rows routed to each held expert
and every token's selected experts.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core import cadc as cadc_lib
from repro.core import dendritic
from repro.kernels import cadc_gmm as gmm_lib
from repro.models.lm import ffn as ffn_lib
from repro.models.lm import layers as ll
from repro.parallel import act_sharding as sa

Array = jnp.ndarray


def _expert_linear_init(key, n_e: int, d_in: int, d_out: int, cfg: ArchConfig):
    std = 1.0 / jnp.sqrt(d_in)
    if cfg.linear_impl == "cadc":
        s = cadc_lib.num_segments(d_in, cfg.crossbar_size)
        w = jax.random.normal(
            key, (n_e, s * cfg.crossbar_size, d_out), jnp.float32) * std
        if s * cfg.crossbar_size > d_in:
            w = w.at[:, d_in:].set(0.0)
        return w.reshape(n_e, s, cfg.crossbar_size, d_out)
    return jax.random.normal(key, (n_e, d_in, d_out), jnp.float32) * std


def _expert_linear(w: Array, x: Array, cfg: ArchConfig) -> Array:
    """w [E, d_in, d_out] or [E, S, xbar, d_out]; x [E, C, d_in]."""
    dt = ll.cdtype(cfg)
    if w.ndim == 4:  # CADC segmented
        e, s, xbar, d_out = w.shape
        xp = cadc_lib.pad_to_segments(x, -1, xbar)
        xs = xp.reshape(*x.shape[:-1], s, xbar).astype(dt)
        f = dendritic.get(cfg.dendritic_fn)
        psums = jnp.einsum("ecsk,eskn->ecsn", xs, w.astype(dt),
                           preferred_element_type=jnp.float32)
        return jnp.sum(f(psums), axis=-2).astype(dt)
    return jnp.einsum("ecd,edn->ecn", x.astype(dt), w.astype(dt),
                      preferred_element_type=jnp.float32).astype(dt)


def moe_init(key, cfg: ArchConfig) -> Dict:
    m = cfg.moe
    d = cfg.d_model
    keys = jax.random.split(key, 6)
    e = m.held
    p = {
        "router": jax.random.normal(keys[0], (d, m.n_experts), jnp.float32)
        * (d ** -0.5),
        "w_gate": _expert_linear_init(keys[1], e, d, m.d_expert, cfg),
        "w_up": _expert_linear_init(keys[2], e, d, m.d_expert, cfg),
        "w_down": _expert_linear_init(keys[3], e, m.d_expert, d, cfg),
    }
    if m.expert_bias:
        p["expert_bias"] = jnp.zeros((m.n_experts,), jnp.float32)
    if m.n_shared > 0:
        shared_cfg = cfg.with_overrides(ffn_type="swiglu")
        p["shared"] = ffn_lib.ffn_init(keys[4], shared_cfg, d_ff=m.d_shared)
        p["shared_gate"] = jax.random.normal(keys[5], (d, 1), jnp.float32) * 0.02
    return p


def capacity(n_tokens: int, cfg: ArchConfig) -> int:
    m = cfg.moe
    c = int(n_tokens * m.top_k * m.capacity_factor / m.n_experts)
    return max(8, -(-c // 8) * 8)  # multiple of 8, >= 8


def _route(p: Dict, tokens: Array, cfg: ArchConfig
           ) -> Tuple[Array, Array, Array]:
    """(top-k weights [T, k], expert ids [T, k], per-expert scores [T, E])
    of the router over all n_experts."""
    m = cfg.moe
    logits = jnp.dot(tokens.astype(jnp.float32), p["router"],
                     precision=jax.lax.Precision.HIGHEST)      # [T, E]
    if m.score == "sigmoid":
        probs = jax.nn.sigmoid(logits)
        choice = probs + p["expert_bias"] if m.expert_bias else probs
        _, top_e = jax.lax.top_k(choice, m.top_k)
        top_w = jnp.take_along_axis(probs, top_e, axis=-1)
        return top_w / (jnp.sum(top_w, -1, keepdims=True) + 1e-6), top_e, \
            probs
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, m.top_k)          # [T, k]
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)  # renormalize
    return top_w, top_e, probs


def _grouped_experts(p: Dict, tokens: Array, top_w: Array, top_e: Array,
                     valid, cfg: ArchConfig) -> Tuple[Array, Array]:
    """Dropless share: the (token, expert) pairs routed to the held
    experts, sorted by expert into whole row tiles, through the grouped
    CADC matmul; both moves are gathers (rows in, each token's k results
    out). Returns (y [T, d] float32, rows routed to each held expert
    [E_here])."""
    m = cfg.moe
    t, d = tokens.shape
    e_here, n_pairs = m.held, t * m.top_k
    with jax.named_scope("moe.dispatch"):
        local = top_e.reshape(-1) - m.first_expert
        held = (local >= 0) & (local < e_here)
        if valid is not None:
            held &= jnp.repeat(valid.reshape(-1), m.top_k)
        key = jnp.where(held, local, e_here)       # elsewhere sorts last
        rows = jnp.sum(key[:, None] == jnp.arange(e_here)[None, :], axis=0,
                       dtype=jnp.int32)            # [E_here]
        bm = gmm_lib.block_m_for(n_pairs, e_here)
        n_tiles = gmm_lib.tile_bound(n_pairs, e_here, bm)
        start, _, _ = gmm_lib.tile_layout(rows, bm, n_tiles)
        order = jnp.argsort(key, stable=True)
        se = key[order]
        se_c = jnp.minimum(se, e_here - 1)
        pos = jnp.arange(n_pairs) - (jnp.cumsum(rows) - rows)[se_c]
        # each pair's row in the tile layout (n_tiles * bm: none)
        dest = jnp.zeros(n_pairs, jnp.int32).at[order].set(
            jnp.where(se < e_here, start[se_c] + pos, n_tiles * bm))
        src = jnp.full(n_tiles * bm, t, jnp.int32).at[dest].set(
            jnp.arange(n_pairs, dtype=jnp.int32) // m.top_k, mode="drop")
        xs = tokens.astype(ll.cdtype(cfg)).at[src].get(mode="fill",
                                                       fill_value=0)

    with jax.named_scope("moe.experts"):
        g = jax.nn.silu(_grouped_linear(p["w_gate"], xs, rows, bm, cfg))
        u = _grouped_linear(p["w_up"], xs, rows, bm, cfg)
        out = _grouped_linear(p["w_down"], g * u, rows, bm, cfg)

    with jax.named_scope("moe.combine"):
        got = out.at[dest.reshape(t, m.top_k)].get(mode="fill",
                                                   fill_value=0)
        w = jnp.where(held, top_w.reshape(-1), 0.0).reshape(t, m.top_k)
        y = jnp.sum(got.astype(jnp.float32) * w[..., None], axis=1)
    return y, rows


def _grouped_linear(w: Array, x: Array, rows: Array, bm: int,
                    cfg: ArchConfig) -> Array:
    """x [R, d_in] in the grouped tile layout through the held experts'
    weights w [E, S, xbar, d_out] (CADC) or [E, d_in, d_out] (dense)."""
    from repro.kernels import ops as kops

    dt = ll.cdtype(cfg)
    if w.ndim == 4:
        xbar = w.shape[2]
        xp = cadc_lib.pad_to_segments(x, -1, xbar).astype(dt)
        return kops.cadc_gmm(xp, w.astype(dt), rows, crossbar_size=xbar,
                             fn=cfg.dendritic_fn, block_m=bm,
                             impl=cfg.kernel_impl)
    return kops.cadc_gmm(x.astype(dt), w.astype(dt), rows,
                         crossbar_size=x.shape[-1], fn="identity",
                         block_m=bm, impl="xla")


def moe_apply(p: Dict, x: Array, cfg: ArchConfig, valid=None
              ) -> Tuple[Array, Array, Dict[str, Array]]:
    """x [B, S, d] -> (y [B, S, d], aux_loss scalar, routed): routed
    holds 'rows', the rows routed to each held expert [E_here] int32, and
    'top', each token's selected experts [B*S, k] int32 (ids over all
    n_experts, in the router's order). `valid` [B, S] (optional) marks the
    rows that are real tokens; the rest are routed nowhere (padding of a
    batched prefill)."""
    m = cfg.moe
    b, s_, d = x.shape
    t = b * s_
    tokens = x.reshape(t, d)

    with jax.named_scope("moe.router"):
        top_w, top_e, probs = _route(p, tokens, cfg)

        # load-balance aux (Switch): E * sum_e f_e * P_e
        f_e = jnp.mean(
            jnp.sum(jax.nn.one_hot(top_e, m.n_experts), axis=1), axis=0
        )
        p_e = jnp.mean(probs, axis=0)
        aux = m.n_experts * jnp.sum(f_e * p_e)

    if m.capacity_factor <= 0:
        y, rows = _grouped_experts(p, tokens, top_w, top_e, valid, cfg)
    else:
        if m.held != m.n_experts:
            raise ValueError("a layer holding a share of the experts needs "
                             "capacity_factor 0 (no drops)")
        y, rows = _capacity_experts(p, tokens, top_w, top_e, cfg)

    if m.n_shared > 0:
        shared_cfg = cfg.with_overrides(ffn_type="swiglu")
        sh = ffn_lib.ffn_apply(p["shared"], tokens, shared_cfg)
        gate = jax.nn.sigmoid(tokens.astype(jnp.float32) @ p["shared_gate"])
        y = y + sh.astype(jnp.float32) * gate

    return y.reshape(b, s_, d).astype(x.dtype), aux, {"rows": rows,
                                                      "top": top_e}


def _capacity_experts(p: Dict, tokens: Array, top_w: Array, top_e: Array,
                      cfg: ArchConfig) -> Tuple[Array, Array]:
    """All experts held, pairs past each expert's capacity dropped."""
    m = cfg.moe
    t, d = tokens.shape

    c = capacity(t, cfg)
    flat_e = top_e.reshape(-1)                          # [T*k]
    flat_t = jnp.repeat(jnp.arange(t), m.top_k)         # token of each pair
    flat_w = top_w.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    se, st_, sw = flat_e[order], flat_t[order], flat_w[order]
    first = jnp.searchsorted(se, se, side="left")
    pos = jnp.arange(t * m.top_k) - first               # position within expert
    keep = pos < c
    # OOB sentinel = buffer size (E*c): stays in int32 range even at
    # 1M-token batches (t*k*c would overflow int32 there).
    dest = jnp.where(keep, se * c + pos, m.n_experts * c)  # OOB -> dropped

    buf = jnp.zeros((m.n_experts * c, d), ll.cdtype(cfg))
    buf = buf.at[dest].set(tokens[st_].astype(buf.dtype), mode="drop")
    ein = buf.reshape(m.n_experts, c, d)

    # EP when E divides the model axis, else expert-TP on the hidden dim:
    # pins GSPMD to sharded expert compute instead of gathering expert
    # weights. Mirrors the param rules in parallel/sharding.
    ax = sa.current_axis_sizes().get("model", 1)
    ep_ok = ax > 1 and m.n_experts % ax == 0

    def _etp(t):
        if ep_ok:
            return sa.shard_act(t, "model", sa.U, sa.U,
                                enabled=cfg.act_sharding)
        return sa.shard_act(t, sa.U, sa.U, "model", enabled=cfg.act_sharding)

    g = jax.nn.silu(_etp(_expert_linear(p["w_gate"], ein, cfg)))
    u = _etp(_expert_linear(p["w_up"], ein, cfg))
    eout = _expert_linear(p["w_down"], g * u, cfg)      # [E, C, d]

    gathered = eout.reshape(m.n_experts * c, d).at[dest].get(
        mode="fill", fill_value=0.0
    )                                                    # [T*k, d], dropped=0
    y = jnp.zeros((t, d), jnp.float32)
    y = y.at[st_].add(gathered.astype(jnp.float32) * sw[:, None])

    rows = jnp.sum(jax.nn.one_hot(flat_e, m.n_experts, dtype=jnp.int32),
                   axis=0)
    return y, rows
