"""xLSTM blocks [arXiv:2405.04517]: mLSTM (matrix memory, pre-up-projection
block) and sLSTM (scalar memory with recurrent gate weights).

Both use the stabilized exponential gating of the paper (max-state m_t).
Training runs a lax.scan over time (the faithful recurrence; the chunkwise
parallel form is a §Perf optimization, see EXPERIMENTS.md). Decode carries
(C, n, m) / (c, n, m, h) states — this IS the xLSTM constant-memory
inference story, which is why long_500k runs for this arch.

All weight matmuls route through CADC-able linears; the recurrence itself is
element/outer-product state arithmetic — no weight crossbar — so the paper's
technique is inapplicable there (DESIGN.md §4).
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.lm import layers as ll

Array = jnp.ndarray
PROJ_FACTOR_M = 2.0       # mLSTM up-projection factor
PROJ_FACTOR_S = 4.0 / 3.0  # sLSTM post-projection factor


def _causal_conv1d_init(key, width: int, ch: int) -> Dict:
    return {"w": jax.random.normal(key, (width, ch), jnp.float32) / width,
            "b": jnp.zeros((ch,), jnp.float32)}


def _causal_conv1d(p: Dict, x: Array) -> Array:
    """Depthwise causal conv. x [B, S, C]."""
    w = p["w"].astype(x.dtype)
    width = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    y = sum(
        xp[:, i : i + x.shape[1], :] * w[i]
        for i in range(width)
    )
    return y + p["b"].astype(x.dtype) if "b" in p else y


def _conv1d_step(p: Dict, buf: Array, x_t: Array) -> Tuple[Array, Array]:
    """Decode step. buf [B, width-1, C] holds previous inputs."""
    w = p["w"].astype(x_t.dtype)
    width = w.shape[0]
    window = jnp.concatenate([buf, x_t[:, None, :]], axis=1)  # [B, width, C]
    y = jnp.einsum("bwc,wc->bc", window, w)
    if "b" in p:
        y = y + p["b"].astype(x_t.dtype)
    return y, window[:, 1:]


# ---------------------------------------------------------------------------
# mLSTM
# ---------------------------------------------------------------------------

class MLSTMState(NamedTuple):
    C: Array       # [B, H, dh, dh]
    n: Array       # [B, H, dh]
    m: Array       # [B, H]
    conv: Array    # [B, width-1, d_inner]


def mlstm_init(key, cfg: ArchConfig) -> Dict:
    d = cfg.d_model
    di = int(PROJ_FACTOR_M * d)
    keys = jax.random.split(key, 8)
    return {
        "norm": ll.rmsnorm_init(d),
        "w_up": ll.linear_init(keys[0], d, 2 * di, cfg),
        "conv": _causal_conv1d_init(keys[1], cfg.conv1d_width, di),
        "w_q": ll.linear_init(keys[2], di, di, cfg),
        "w_k": ll.linear_init(keys[3], di, di, cfg),
        "w_v": ll.linear_init(keys[4], di, di, cfg),
        "w_if": ll.linear_init(keys[5], di, 2 * cfg.n_heads, cfg, bias=True),
        "out_norm": ll.rmsnorm_init(di),
        "w_down": ll.linear_init(keys[6], di, d, cfg),
    }


def _mlstm_cell(state, qkvif, *, dh: int):
    """One timestep of the stabilized mLSTM recurrence."""
    C, n, m = state
    q, k, v, i_raw, f_raw = qkvif
    # q,k,v: [B, H, dh]; i_raw, f_raw: [B, H]
    f_log = jax.nn.log_sigmoid(f_raw.astype(jnp.float32))
    i_log = i_raw.astype(jnp.float32)
    m_new = jnp.maximum(f_log + m, i_log)
    f_p = jnp.exp(f_log + m - m_new)[..., None]
    i_p = jnp.exp(i_log - m_new)[..., None]
    k32, v32, q32 = (t.astype(jnp.float32) for t in (k, v, q))
    k32 = k32 / jnp.sqrt(dh)
    C_new = f_p[..., None] * C + i_p[..., None] * (
        v32[..., :, None] * k32[..., None, :]
    )
    n_new = f_p * n + i_p * k32
    num = jnp.einsum("bhij,bhj->bhi", C_new, q32)
    den = jnp.maximum(
        jnp.abs(jnp.einsum("bhj,bhj->bh", n_new, q32)), jnp.exp(-m_new)
    )[..., None]
    h = num / den
    return (C_new, n_new, m_new), h


def _mlstm_qkvif(p: Dict, x: Array, cfg: ArchConfig):
    b, s, d = x.shape
    h_heads, di = cfg.n_heads, int(PROJ_FACTOR_M * d)
    dh = di // h_heads
    xn = ll.rmsnorm_apply(p["norm"], x, cfg.norm_eps)
    up = ll.linear_apply(p["w_up"], xn, cfg)
    x_in, z = jnp.split(up, 2, axis=-1)
    conv_out = jax.nn.silu(_causal_conv1d(p["conv"], x_in))
    q = ll.linear_apply(p["w_q"], conv_out, cfg).reshape(b, s, h_heads, dh)
    k = ll.linear_apply(p["w_k"], conv_out, cfg).reshape(b, s, h_heads, dh)
    v = ll.linear_apply(p["w_v"], x_in, cfg).reshape(b, s, h_heads, dh)
    if_gates = ll.linear_apply(p["w_if"], x_in, cfg).reshape(b, s, 2, h_heads)
    return q, k, v, if_gates[:, :, 0], if_gates[:, :, 1], z, dh, di


def _mlstm_out(p: Dict, h: Array, z: Array, cfg: ArchConfig) -> Array:
    h = ll.rmsnorm_apply(p["out_norm"], h, cfg.norm_eps)
    h = h * jax.nn.silu(z)
    return ll.linear_apply(p["w_down"], h, cfg)


def mlstm_apply(p: Dict, x: Array, cfg: ArchConfig) -> Array:
    """Training path. Chunkwise-parallel by default (§Perf iter 3):
    the token-by-token scan writes the [B,H,dh,dh] matrix memory to HBM
    every step (and autodiff saves it per step) — the audit measured
    2.8e14 bytes/chip/step for xlstm_13b train_4k, 60x the arithmetic's
    need. The chunkwise form (as in the mLSTM/TFLA literature) telescopes
    the stabilized recurrence over chunks of L tokens: within-chunk work
    becomes decay-masked attention-style matmuls (MXU-friendly), and the
    matrix memory is materialized once per CHUNK instead of once per
    token. cfg.mlstm_chunk=0 selects the sequential oracle (tests assert
    equivalence)."""
    b, s, d = x.shape
    h_heads = cfg.n_heads
    q, k, v, i_raw, f_raw, z, dh, di = _mlstm_qkvif(p, x, cfg)
    chunk = getattr(cfg, "mlstm_chunk", 256)
    if chunk and s % chunk == 0 and s > chunk:
        h = _mlstm_chunkwise(q, k, v, i_raw, f_raw, chunk=chunk, dh=dh)
    else:
        def step(carry, inp):
            new_carry, hh = _mlstm_cell(carry, inp, dh=dh)
            return new_carry, hh

        init = (
            jnp.zeros((b, h_heads, dh, dh), jnp.float32),
            jnp.zeros((b, h_heads, dh), jnp.float32),
            jnp.full((b, h_heads), -jnp.inf, jnp.float32),
        )
        xs = (
            jnp.moveaxis(q, 1, 0), jnp.moveaxis(k, 1, 0),
            jnp.moveaxis(v, 1, 0),
            jnp.moveaxis(i_raw, 1, 0), jnp.moveaxis(f_raw, 1, 0),
        )
        _, hs = jax.lax.scan(step, init, xs)
        h = jnp.moveaxis(hs, 0, 1)
    h = h.reshape(b, s, di).astype(x.dtype)
    return _mlstm_out(p, h, z, cfg)


def _mlstm_chunkwise(q, k, v, i_raw, f_raw, *, chunk: int, dh: int) -> Array:
    """Stabilized chunkwise mLSTM. q/k/v [B,S,H,dh]; i/f [B,S,H].

    Sequential recurrence (cell above):
        m_t = max(f_t + m_{t-1}, i_t)                      (log-space max)
        C_t = e^{f_t + m_{t-1} - m_t} C_{t-1} + e^{i_t - m_t} v_t k_t^T
        n_t likewise;  h_t = C_t q_t / max(|n_t q_t|, e^{-m_t})
    telescopes over a chunk (b_j = within-chunk cumsum of f-logs):
        m_j = max(b_j + m_0, max_{tau<=j}(b_j - b_tau + i_tau))
        C_j = e^{b_j + m_0 - m_j} C_0 + sum_tau e^{a_jtau - m_j} v k^T,
        a_jtau = b_j - b_tau + i_tau  (tau <= j)
    so per chunk: inter = (scaled q) @ C_0, intra = (D o QK^T) V with the
    decay matrix D_jtau = e^{a_jtau - m_j} — all matmuls."""
    b, s, h, _ = q.shape
    nc = s // chunk

    def resh(t, last):
        return jnp.moveaxis(t.reshape(b, nc, chunk, h, *last), 3, 2) \
            .astype(jnp.float32)  # [B, nc, H, L, *last]

    qf = resh(q, (dh,))
    kf = resh(k, (dh,)) / jnp.sqrt(dh)
    vf = resh(v, (dh,))
    i_log = resh(i_raw, ())                       # [B, nc, H, L]
    f_log = jax.nn.log_sigmoid(resh(f_raw, ()))

    bcum = jnp.cumsum(f_log, axis=-1)             # b_j, [B, nc, H, L]
    B_tot = bcum[..., -1]                         # full-chunk decay

    # intra-chunk decay matrix exponents: a[j, tau] = b_j - b_tau + i_tau
    a = (bcum[..., :, None] - bcum[..., None, :] + i_log[..., None, :])
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    a = jnp.where(causal, a, -jnp.inf)            # [B, nc, H, L, L]
    a_max = jnp.max(a, axis=-1)                   # max_tau a[j, tau]

    def chunk_step(carry, xs):
        C0, n0, m0 = carry                        # [B,H,dh,dh] [B,H,dh] [B,H]
        qc, kc, vc, bc, Bc, ac, acm, ic = xs
        # m_j = max(b_j + m0, max_tau a[j, tau])
        m_j = jnp.maximum(bc + m0[:, :, None], acm)         # [B,H,L]
        inter_scale = jnp.exp(bc + m0[:, :, None] - m_j)    # [B,H,L]
        D = jnp.exp(ac - m_j[..., None])                    # [B,H,L,L]
        scores = jnp.einsum("bhld,bhtd->bhlt", qc, kc) * D
        num = (jnp.einsum("bhlt,bhtd->bhld", scores, vc)
               + inter_scale[..., None]
               * jnp.einsum("bhld,bhed->bhle", qc, C0))  # contract k-dim of C
        nvec = (jnp.einsum("bhlt,bhtd->bhld", D, kc)
                + inter_scale[..., None] * n0[:, :, None, :])
        den = jnp.maximum(jnp.abs(jnp.einsum("bhld,bhld->bhl", nvec, qc)),
                          jnp.exp(-m_j))
        hc = num / den[..., None]                           # [B,H,L,dh]

        # carry to the next chunk (j = L row of the same telescopes)
        m_L = m_j[..., -1]
        w_in = jnp.exp(ac[..., -1, :] - m_L[..., None])     # [B,H,L]
        C_L = (jnp.exp(Bc + m0 - m_L)[..., None, None] * C0
               + jnp.einsum("bht,bhtd,bhte->bhde", w_in, vc, kc))
        n_L = (jnp.exp(Bc + m0 - m_L)[..., None] * n0
               + jnp.einsum("bht,bhtd->bhd", w_in, kc))
        return (C_L, n_L, m_L), hc

    init = (
        jnp.zeros((b, h, dh, dh), jnp.float32),
        jnp.zeros((b, h, dh), jnp.float32),
        jnp.full((b, h), -jnp.inf, jnp.float32),
    )
    xs = tuple(jnp.moveaxis(t, 1, 0) for t in
               (qf, kf, vf, bcum, B_tot, a, a_max, i_log))
    _, hs = jax.lax.scan(chunk_step, init, xs)              # [nc,B,H,L,dh]
    hs = jnp.moveaxis(hs, 0, 2)                             # [B,H,nc,L,dh]
    return hs.reshape(b, h, s, dh).transpose(0, 2, 1, 3)    # [B,S,H,dh]


def mlstm_init_state(cfg: ArchConfig, batch: int) -> MLSTMState:
    d = cfg.d_model
    di = int(PROJ_FACTOR_M * d)
    h_heads = cfg.n_heads
    dh = di // h_heads
    return MLSTMState(
        C=jnp.zeros((batch, h_heads, dh, dh), jnp.float32),
        n=jnp.zeros((batch, h_heads, dh), jnp.float32),
        m=jnp.full((batch, h_heads), -jnp.inf, jnp.float32),
        conv=jnp.zeros((batch, cfg.conv1d_width - 1, di), jnp.float32),
    )


def mlstm_decode(p: Dict, x: Array, cfg: ArchConfig,
                 state: MLSTMState) -> Tuple[Array, MLSTMState]:
    """x [B, 1, d] one token."""
    b, _, d = x.shape
    h_heads, di = cfg.n_heads, int(PROJ_FACTOR_M * d)
    dh = di // h_heads
    xn = ll.rmsnorm_apply(p["norm"], x, cfg.norm_eps)[:, 0]
    up = ll.linear_apply(p["w_up"], xn, cfg)
    x_in, z = jnp.split(up, 2, axis=-1)
    conv_out, new_buf = _conv1d_step(p["conv"], state.conv.astype(x_in.dtype), x_in)
    conv_out = jax.nn.silu(conv_out)
    q = ll.linear_apply(p["w_q"], conv_out, cfg).reshape(b, h_heads, dh)
    k = ll.linear_apply(p["w_k"], conv_out, cfg).reshape(b, h_heads, dh)
    v = ll.linear_apply(p["w_v"], x_in, cfg).reshape(b, h_heads, dh)
    if_g = ll.linear_apply(p["w_if"], x_in, cfg).reshape(b, 2, h_heads)
    (C, n, m), h = _mlstm_cell(
        (state.C, state.n, state.m), (q, k, v, if_g[:, 0], if_g[:, 1]), dh=dh
    )
    h = h.reshape(b, di).astype(x.dtype)
    h = ll.rmsnorm_apply(p["out_norm"], h, cfg.norm_eps)
    h = h * jax.nn.silu(z)
    y = ll.linear_apply(p["w_down"], h, cfg)[:, None, :]
    return y, MLSTMState(C, n, m, new_buf.astype(jnp.float32))


# ---------------------------------------------------------------------------
# sLSTM
# ---------------------------------------------------------------------------

class SLSTMState(NamedTuple):
    c: Array   # [B, H, dh]
    n: Array
    m: Array   # [B, H, dh] (per-unit stabilizer)
    h: Array


def slstm_init(key, cfg: ArchConfig) -> Dict:
    d = cfg.d_model
    h_heads = cfg.n_heads
    dh = d // h_heads
    dp = int(PROJ_FACTOR_S * d)
    keys = jax.random.split(key, 8)
    return {
        "norm": ll.rmsnorm_init(d),
        "w_gates": ll.linear_init(keys[0], d, 4 * d, cfg, bias=True),
        # recurrent weights: block-diagonal per head [4, H, dh, dh]
        "r_gates": jax.random.normal(keys[1], (4, h_heads, dh, dh), jnp.float32)
        / jnp.sqrt(dh),
        "out_norm": ll.rmsnorm_init(d),
        "w_up_gate": ll.linear_init(keys[2], d, dp, cfg),
        "w_up": ll.linear_init(keys[3], d, dp, cfg),
        "w_down": ll.linear_init(keys[4], dp, d, cfg),
    }


def _slstm_cell(state: SLSTMState, wx: Array, r: Array):
    """wx [B, 4, H, dh] pre-activations from the input; r [4,H,dh,dh]."""
    c, n, m, h_prev = state
    rec = jnp.einsum("ghij,bhj->bghi", r, h_prev)  # [B,4,H,dh]
    pre = wx.astype(jnp.float32) + rec
    i_raw, f_raw, z_raw, o_raw = (pre[:, g] for g in range(4))
    f_log = jax.nn.log_sigmoid(f_raw)
    m_new = jnp.maximum(f_log + m, i_raw)
    i_p = jnp.exp(i_raw - m_new)
    f_p = jnp.exp(f_log + m - m_new)
    c_new = f_p * c + i_p * jnp.tanh(z_raw)
    n_new = f_p * n + i_p
    h_new = jax.nn.sigmoid(o_raw) * c_new / jnp.maximum(n_new, 1e-6)
    return SLSTMState(c_new, n_new, m_new, h_new), h_new


def slstm_apply(p: Dict, x: Array, cfg: ArchConfig) -> Array:
    b, s, d = x.shape
    h_heads = cfg.n_heads
    dh = d // h_heads
    xn = ll.rmsnorm_apply(p["norm"], x, cfg.norm_eps)
    wx = ll.linear_apply(p["w_gates"], xn, cfg).reshape(b, s, 4, h_heads, dh)

    def step(carry, wx_t):
        return _slstm_cell(carry, wx_t, p["r_gates"])

    zeros = jnp.zeros((b, h_heads, dh), jnp.float32)
    init = SLSTMState(zeros, zeros, jnp.full_like(zeros, -jnp.inf), zeros)
    _, hs = jax.lax.scan(step, init, jnp.moveaxis(wx, 1, 0))
    h = jnp.moveaxis(hs, 0, 1).reshape(b, s, d).astype(x.dtype)
    h = ll.rmsnorm_apply(p["out_norm"], h, cfg.norm_eps)
    # post up/down projection (GeGLU, PF 4/3)
    u = jax.nn.gelu(ll.linear_apply(p["w_up_gate"], h, cfg), approximate=True)
    v = ll.linear_apply(p["w_up"], h, cfg)
    return ll.linear_apply(p["w_down"], u * v, cfg)


def slstm_init_state(cfg: ArchConfig, batch: int) -> SLSTMState:
    dh = cfg.d_model // cfg.n_heads
    shape = (batch, cfg.n_heads, dh)
    # one buffer a field: a cache pytree is donated to the decode program
    return SLSTMState(jnp.zeros(shape, jnp.float32),
                      jnp.zeros(shape, jnp.float32),
                      jnp.full(shape, -jnp.inf, jnp.float32),
                      jnp.zeros(shape, jnp.float32))


def slstm_decode(p: Dict, x: Array, cfg: ArchConfig,
                 state: SLSTMState) -> Tuple[Array, SLSTMState]:
    b, _, d = x.shape
    h_heads = cfg.n_heads
    dh = d // h_heads
    xn = ll.rmsnorm_apply(p["norm"], x, cfg.norm_eps)[:, 0]
    wx = ll.linear_apply(p["w_gates"], xn, cfg).reshape(b, 4, h_heads, dh)
    new_state, h = _slstm_cell(state, wx, p["r_gates"])
    h = h.reshape(b, d).astype(x.dtype)
    h = ll.rmsnorm_apply(p["out_norm"], h, cfg.norm_eps)
    u = jax.nn.gelu(ll.linear_apply(p["w_up_gate"], h, cfg), approximate=True)
    v = ll.linear_apply(p["w_up"], h, cfg)
    y = ll.linear_apply(p["w_down"], u * v, cfg)[:, None, :]
    return y, new_state
