"""Plain reference of the CADC decoder the phi4-mini-cadc cells serve.

Phi-4-mini's published block (arXiv:2503.01743; microsoft/Phi-4-mini-
instruct config.json): pre-norm RMSNorm, grouped-query attention with
rotary positions, SwiGLU feed-forward, tied input and output embeddings.
Every linear of every layer is a CADC linear: its contraction is cut into
crossbar segments of `xbar` rows and y = sum_s relu(x_s @ w_s)
(arXiv:2511.22166, eq. 4). The LM head is the plain tied product.

Departures from the published model, shared with the program it checks:
rotary on every channel of a head (the published model rotates 75% with
LongRoPE), and the norm scale stored as (1 + scale).

Full sequence, no cache, float32 with every product at "highest"
precision. `quant="fp8"` instead rounds both operands of every product
to float8 e4m3 on a per-tensor scale: the control that a bfloat16 run has
to beat. `quant="bf16"` rounds them to bfloat16, as the program's own
products are: a measure of the noise a sound run may show.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _q(a, quant: Optional[str]):
    """`a` with its values rounded as a product's operand in `quant`."""
    if quant is None:
        return a
    if quant == "bf16":
        return a.astype(jnp.bfloat16).astype(jnp.float32)
    if quant != "fp8":
        raise ValueError(f"unknown quant {quant!r}")
    s = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / 448.0
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _cadc(x, w, xbar: int, quant):
    """x [T, D] through a CADC linear with weights w [S, xbar, N]."""
    s = w.shape[0]
    xs = _q(x, quant).reshape(x.shape[0], s, xbar)
    psums = jnp.einsum("tsk,skn->tsn", xs, _q(w, quant), precision=HIGHEST)
    return jnp.sum(jnp.maximum(psums, 0.0), axis=1)


def _rmsnorm(x, scale, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + scale)


def _rope(x, pos, theta: float):
    """x [T, H, hd]: rotate the two halves of each head by pos * freq."""
    half = x.shape[-1] // 2
    freq = jnp.exp(-jnp.log(theta) * jnp.arange(half) / half)
    ang = pos[:, None].astype(jnp.float32) * freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(x, lp, cfg: Dict, quant):
    t = x.shape[0]
    h_, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    xbar, eps = cfg["crossbar_size"], cfg["rms_norm_eps"]
    pos = jnp.arange(t)
    a = lp["attn"]
    h = _rmsnorm(x, lp["ln1"]["scale"], eps)
    q = _rope(_cadc(h, a["wq"]["w"], xbar, quant).reshape(t, h_, hd), pos,
              cfg["rope_theta"])
    k = _rope(_cadc(h, a["wk"]["w"], xbar, quant).reshape(t, kv, hd), pos,
              cfg["rope_theta"])
    v = _cadc(h, a["wv"]["w"], xbar, quant).reshape(t, kv, hd)
    g = h_ // kv
    qg = q.reshape(t, kv, g, hd)
    scores = jnp.einsum("qkgd,lkd->kgql", _q(qg, quant), _q(k, quant),
                        precision=HIGHEST) \
        / jnp.sqrt(jnp.float32(hd))
    causal = pos[None, :] <= pos[:, None]
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("kgql,lkd->qkgd", _q(probs, quant), _q(v, quant),
                     precision=HIGHEST)
    x = x + _cadc(out.reshape(t, h_ * hd), a["wo"]["w"], xbar, quant)
    f = lp["ffn"]
    h = _rmsnorm(x, lp["ln2"]["scale"], eps)
    u = jax.nn.silu(_cadc(h, f["w_gate"]["w"], xbar, quant)) \
        * _cadc(h, f["w_up"]["w"], xbar, quant)
    return x + _cadc(u, f["w_down"]["w"], xbar, quant)


def forward(params: Dict, tokens, cfg: Dict, quant: Optional[str] = None):
    """Logits [T, vocab] of one sequence tokens [T]. params: the stacked
    weights (`units[0]`, leading axis = layer), embedding `embed.table`
    and `final_norm.scale`."""
    table = params["embed"]["table"][: cfg["vocab_size"]]
    x = table[tokens]

    def body(x, lp):
        return _layer(x, lp, cfg, quant), None

    x, _ = jax.lax.scan(body, x, params["units"][0])
    x = _rmsnorm(x, params["final_norm"]["scale"], cfg["rms_norm_eps"])
    return jnp.einsum("td,vd->tv", _q(x, quant), _q(table, quant),
                      precision=HIGHEST)


def logits_fn(cfg: Dict, quant: Optional[str] = None):
    """`forward` as one jitted function (params, tokens) -> logits."""
    return jax.jit(lambda p, t: forward(p, t, cfg, quant))
