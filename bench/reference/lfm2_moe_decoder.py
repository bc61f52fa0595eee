"""Plain reference of the CADC LFM2-8B-A1B decoder the lfm2 cell serves.

LFM2-8B-A1B (LiquidAI; config.json of LiquidAI/LFM2-8B-A1B, and the
`lfm2_moe` modeling code of HF transformers). Each layer is
h = x + mixer(rmsnorm(x)), then out = h + ffn(rmsnorm(h)):

* `conv` mixer: [B; C; u] = in_proj(x) (d -> 3d), v = causal depthwise
  conv of width 3 over B * u (no bias), y = out_proj(C * v);
* `full_attention` mixer: q = rmsnorm_hd(W_q x), k = rmsnorm_hd(W_k x)
  per head (QK-norm), rotary on all 64 channels at theta 1e6, causal GQA
  (32 query, 8 KV heads), W_o;
* the first `num_dense_layers` FFNs: W_down(silu(W_gate x) * W_up x);
* the rest MoE: s = sigmoid(W_r x) over all experts (W_r plain float32),
  top-k of s + b (b the expert bias, selection only),
  w = s[sel] / (sum s[sel] + 1e-6) * routed_scale, and
  y = sum over the selected experts this chip holds of w_e SwiGLU_e(x):
  the chip's share of the layer, as the program computes it. The check
  may put the program's own selection in place of sel (`forced`), so
  that a near tie the program's rounding broke the other way moves
  nothing downstream; the reference then still reports its own choice,
  and the caller counts where the two differ;
* a final norm and the tied head.

Every linear except the router and the head is a CADC linear over
`xbar`-row crossbars, y = sum_s relu(x_s @ w_s) (arXiv:2511.22166, eq. 4).

Assumed, in the program and here alike (the configuration's `assumed`):
QK-norm on q and k; head_dim = 2048 / 32 = 64 with rotary on every
channel; SiLU in the dense and expert FFNs; a sigmoid router with a
plain float32 weight; tied embeddings; norm scales stored as (1 + scale);
the expert bias drawn from the seed.

Full sequence, no cache, no batching, float32 with every product at
"highest" precision. `quant="fp8"` rounds both operands of every product
to float8 e4m3 on a per-tensor scale: the control a bfloat16 run has to
beat. `quant="bf16"` rounds them to bfloat16, as the program's own
products are: a measure of the noise a sound run may show, and of the
routing choices that rounding alone can flip. The layers are walked as
the configuration lists them (`layer_types`, `num_dense_layers`), not as
the program's weights happen to be laid out.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

from reference import phi4_decoder
from reference.phi4_decoder import HIGHEST, _q, _rmsnorm, _rope


def _cadc(x, w, xbar: int, quant):
    """x [T, D] through a CADC linear w [S, xbar, N], D padded with zeros
    to S * xbar."""
    pad = w.shape[0] * xbar - x.shape[-1]
    return phi4_decoder._cadc(jnp.pad(x, ((0, 0), (0, pad))), w, xbar, quant)


def layers(params: Dict) -> List[Dict]:
    """Each layer's weights in order, out of the program's layout: the
    leading layers, each repeat of the scanned unit, then the tail."""
    out = list(params.get("lead", ()))
    units = params.get("units", ())
    if units:
        reps = jax.tree_util.tree_leaves(units)[0].shape[0]
        for r in range(reps):
            out += [jax.tree_util.tree_map(lambda a, r=r: a[r], u)
                    for u in units]
    return out + list(params["tail"])


def _attention(x, a, cfg: Dict, quant):
    t = x.shape[0]
    h_, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // h_
    xbar, eps = cfg["crossbar_size"], cfg["norm_eps"]
    pos = jnp.arange(t)
    q = _cadc(x, a["wq"]["w"], xbar, quant).reshape(t, h_, hd)
    k = _cadc(x, a["wk"]["w"], xbar, quant).reshape(t, kv, hd)
    v = _cadc(x, a["wv"]["w"], xbar, quant).reshape(t, kv, hd)
    q = _rope(_rmsnorm(q, a["q_norm"]["scale"], eps), pos, cfg["rope_theta"])
    k = _rope(_rmsnorm(k, a["k_norm"]["scale"], eps), pos, cfg["rope_theta"])
    qg = q.reshape(t, kv, h_ // kv, hd)
    scores = jnp.einsum("qkgd,lkd->kgql", _q(qg, quant), _q(k, quant),
                        precision=HIGHEST) / jnp.sqrt(jnp.float32(hd))
    causal = pos[None, :] <= pos[:, None]
    probs = jax.nn.softmax(jnp.where(causal[None, None], scores, -jnp.inf),
                           axis=-1)
    out = jnp.einsum("kgql,lkd->qkgd", _q(probs, quant), _q(v, quant),
                     precision=HIGHEST)
    return _cadc(out.reshape(t, h_ * hd), a["wo"]["w"], xbar, quant)


def _shortconv(x, c, cfg: Dict, quant):
    xbar = cfg["crossbar_size"]
    b, gate, u = jnp.split(_cadc(x, c["in_proj"]["w"], xbar, quant), 3,
                           axis=-1)
    bu = b * u
    w = c["conv"]["w"]                       # [L, d]
    width = w.shape[0]
    padded = jnp.pad(bu, ((width - 1, 0), (0, 0)))
    v = sum(padded[i: i + x.shape[0]] * w[i] for i in range(width))
    return _cadc(gate * v, c["out_proj"]["w"], xbar, quant)


def _swiglu(x, f, xbar: int, quant):
    return _cadc(jax.nn.silu(_cadc(x, f["w_gate"]["w"], xbar, quant))
                 * _cadc(x, f["w_up"]["w"], xbar, quant),
                 f["w_down"]["w"], xbar, quant)


def route(x, m, cfg: Dict, quant, forced=None):
    """(the experts used [T, k] ascending, their weights [T, k], the
    router's own choice [T, k] ascending, and its k-th minus (k+1)-th
    choice score [T]). `forced` [T, k] (optional) puts a given selection
    in the router's place on every row whose first entry is not negative:
    the weights are then this router's scores of those experts."""
    s = jax.nn.sigmoid(jnp.einsum("td,de->te", _q(x, quant),
                                  _q(m["router"], quant), precision=HIGHEST))
    choice = s + m["expert_bias"]
    top, own = jax.lax.top_k(choice, cfg["num_experts_per_tok"] + 1)
    own = jnp.sort(own[:, :-1], axis=-1)
    sel = own if forced is None else jnp.sort(
        jnp.where(forced[:, :1] >= 0, forced, own), axis=-1)
    w = jnp.take_along_axis(s, sel, axis=-1)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-6) \
        * cfg["routed_scaling_factor"]
    return sel, w, own, top[:, -2] - top[:, -1]


def _moe(x, m, cfg: Dict, quant, forced=None):
    """The held experts' part of the layer, the router's own choice and
    its margin (`route`)."""
    xbar, first = cfg["crossbar_size"], cfg["first_expert"]
    sel, w, own, margin = route(x, m, cfg, quant, forced)

    def expert(e, ew):
        coef = jnp.sum(jnp.where(sel == first + e, w, 0.0), axis=-1)
        return coef[:, None] * _swiglu(x, ew, xbar, quant)

    ews = {n: {"w": m[n]} for n in ("w_gate", "w_up", "w_down")}
    ys = jax.lax.map(lambda a: expert(a[0], a[1]),
                     (jnp.arange(cfg["num_experts"]), ews))
    return jnp.sum(ys, axis=0), own, margin


def forward(params: Dict, tokens, cfg: Dict, quant: Optional[str] = None,
            forced=None):
    """(logits [T, vocab], per MoE layer the router's own choice
    [n, T, k], its margin [n, T] and the layer's input [n, T, d], and the
    final norm's output [T, d]) of one sequence tokens [T]. The layers follow the configuration's
    `layer_types` and `num_dense_layers`; the program's weights of each
    have to be of that kind. `forced` [n, T, k] (optional): `route`'s,
    layer by layer."""
    eps = cfg["norm_eps"]
    table = params["embed"]["table"][: cfg["vocab_size"]]
    x = table[tokens]
    kinds = cfg["layer_types"][: cfg["num_hidden_layers"]]
    weights = layers(params)
    if len(weights) != len(kinds):
        raise ValueError(f"the program has {len(weights)} layers, the "
                         f"configuration {len(kinds)}")
    owns, margins, inputs = [], [], []
    for i, (kind, lp) in enumerate(zip(kinds, weights)):
        mixer = "attn" if kind == "full_attention" else "shortconv"
        ffn = "ffn" if i < cfg["num_dense_layers"] else "moe"
        if mixer not in lp or ffn not in lp:
            raise ValueError(f"layer {i} is {kind} with a {ffn} FFN, but "
                             f"the program's weights hold {sorted(lp)}")
        h = _rmsnorm(x, lp["ln1"]["scale"], eps)
        if mixer == "attn":
            x = x + _attention(h, lp["attn"], cfg, quant)
        else:
            x = x + _shortconv(h, lp["shortconv"], cfg, quant)
        h = _rmsnorm(x, lp["ln2"]["scale"], eps)
        if ffn == "moe":
            y, own, margin = _moe(h, lp["moe"], cfg, quant,
                                  None if forced is None
                                  else forced[len(owns)])
            owns.append(own)
            margins.append(margin)
            inputs.append(h)
        else:
            y = _swiglu(h, lp["ffn"], cfg["crossbar_size"], quant)
        x = x + y
    x = _rmsnorm(x, params["final_norm"]["scale"], eps)
    logits = jnp.einsum("td,vd->tv", _q(x, quant), _q(table, quant),
                        precision=HIGHEST)
    return (logits, jnp.stack(owns), jnp.stack(margins), jnp.stack(inputs),
            x)


def logits_fn(cfg: Dict, quant: Optional[str] = None):
    """`forward` as one jitted function (params, tokens, forced) ->
    (logits, own choices, margins, MoE layer inputs, final stream);
    `forced` all -1 leaves every choice to the router."""
    return jax.jit(lambda p, t, f: forward(p, t, cfg, quant, f))
