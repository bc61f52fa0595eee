"""Plain reference of ResNet-18 (CIFAR variant) with CADC convolutions.

Written from the paper's equations, not from the program: every conv is
unrolled over its taps (channels fastest: tap (i, j) holds rows
[(i * k + j) * cin, (i * k + j + 1) * cin) of the contraction), cut into
crossbar segments of `xbar` rows, and each segment's partial sum passes
the dendritic ReLU before the segments are summed, s = 0 first:
y = sum_s relu(x_s @ w_s) (CADC, arXiv:2511.22166, eq. 4).

Two arithmetics:

* ``"fp32"``: everything in float32, every product at "highest"
  precision. `products=bfloat16` first rounds both operands of each conv
  and classifier product to bfloat16, as a TPU's default matmul precision
  does; the products and every sum stay float32.
* ``"q8"``: the paper's 4/2/4-bit point. Before every conv and the
  classifier, activations become symmetric integer codes on a per-tensor
  scale (max |x| over the whole batch, `2**(bits-1) - 1` levels), weights
  become ternary codes with the TWN rule (threshold 0.7 mean|w|, alpha the
  mean |w| above it); integer partial sums are rescaled by
  lsb * alpha, pass the ReLU and are summed.

`dtype=bfloat16` computes every value, partial sums and their running sum
included, in bfloat16: the control that a float32 run has to beat.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

STAGES = (2, 2, 2, 2)
BN_EPS = 1e-5
HIGHEST = jax.lax.Precision.HIGHEST


def _patches(x, k: int, stride: int):
    """[B, H, W, C] -> [B, OH, OW, k*k*C], taps outer, channels fastest;
    zero padding (k-1)//2 before and the rest after."""
    lo = (k - 1) // 2
    xp = jnp.pad(x, ((0, 0), (lo, k - 1 - lo), (lo, k - 1 - lo), (0, 0)))
    oh = (x.shape[1] + k - 1 - k) // stride + 1
    ow = (x.shape[2] + k - 1 - k) // stride + 1
    taps = [xp[:, i:i + (oh - 1) * stride + 1:stride,
               j:j + (ow - 1) * stride + 1:stride, :]
            for i in range(k) for j in range(k)]
    return jnp.concatenate(taps, axis=-1)


def _dendritic(x2, w2, xbar: int, scale=None, products=None):
    """sum_s relu(scale * (x_s @ w_s)) over crossbar segments of `xbar`
    rows, summed in order in x2's dtype. `products` (a dtype) rounds both
    operands to it first; the products and their sums stay in x2's
    dtype."""
    if products is not None:
        x2 = x2.astype(products).astype(x2.dtype)
        w2 = w2.astype(products).astype(x2.dtype)
    d = w2.shape[0]
    acc = None
    for lo in range(0, d, xbar):
        p = jnp.einsum("...k,kn->...n", x2[..., lo:lo + xbar],
                       w2[lo:lo + xbar], precision=HIGHEST,
                       preferred_element_type=x2.dtype)
        if scale is not None:
            p = p * scale
        p = jnp.maximum(p, 0)
        acc = p if acc is None else acc + p
    return acc


def _codes(x, bits: int):
    levels = 2 ** (bits - 1) - 1
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-8)
    return jnp.round(jnp.clip(x / scale, -1.0, 1.0) * levels), scale / levels


def _ternary(w):
    a = jnp.abs(w)
    mask = a > 0.7 * jnp.mean(a)
    alpha = jnp.sum(a * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.sign(w) * mask, alpha


def _layer(x, w, xbar: int, quant: Optional[int], products=None,
           patches=lambda t: t):
    """One CADC contraction of activations x with weights w, which flatten
    to [D, N], in the arithmetic asked for; `patches` unrolls x to
    [..., D]. In q8 the codes and their scale are taken over x itself,
    before unrolling, and the ternary statistics over w as stored."""
    w2 = w.reshape(-1, w.shape[-1])
    if quant is None:
        return _dendritic(patches(x), w2.astype(x.dtype), xbar,
                          products=products)
    xc, lsb = _codes(x, quant)
    wc, alpha = _ternary(w)
    return _dendritic(patches(xc), wc.reshape(w2.shape), xbar,
                      scale=lsb * alpha)


def _conv(p, x, stride: int, xbar: int, quant, products=None):
    w = p["w"]
    return _layer(x, w, xbar, quant, products,
                  patches=lambda t: _patches(t, w.shape[0], stride))


def _bn(p, s, x, measured: Optional[Dict] = None):
    """Batch norm with running statistics `s`; with `measured` given, with
    the batch's own statistics instead, which are stored into it."""
    dt = x.dtype
    if measured is not None:
        axes = tuple(range(x.ndim - 1))
        s = {"mean": jnp.mean(x, axes), "var": jnp.var(x, axes)}
        measured.update(s)
    inv = jax.lax.rsqrt(s["var"].astype(dt) + jnp.asarray(BN_EPS, dt))
    return (x - s["mean"].astype(dt)) * inv * p["scale"].astype(dt) \
        + p["bias"].astype(dt)


def forward(params: Dict, state: Dict, x, *, xbar: int = 64,
            quant_bits: Optional[int] = None, dtype=jnp.float32,
            products=None, measured: Optional[Dict] = None):
    """Logits [B, classes] of images x [B, 32, 32, 3]. quant_bits None:
    float arithmetic in `dtype`, with the operands of every product of a
    conv or the classifier rounded to `products` if given; an int: the q8
    arithmetic with activation codes of that many bits. With `measured`
    (a dict), every batch norm normalizes by the batch's own statistics
    and `measured` receives them in the layout of `state`."""
    x = x.astype(dtype)
    relu = lambda t: jnp.maximum(t, 0)

    def bn(p, s, path, t):
        if measured is None:
            return _bn(p, s, t)
        m = measured
        for k in path:
            m = m.setdefault(k, {})
        return _bn(p, s, t, m)

    h = relu(bn(params["bn_stem"], state["bn_stem"], ("bn_stem",),
                _conv(params["stem"], x, 1, xbar, quant_bits, products)))
    for si, n_blocks in enumerate(STAGES):
        for bi in range(n_blocks):
            name = f"s{si}b{bi}"
            p, s = params[name], state[name]
            stride = 2 if (si > 0 and bi == 0) else 1
            y = relu(bn(p["bn1"], s["bn1"], (name, "bn1"),
                        _conv(p["conv1"], h, stride, xbar, quant_bits,
                              products)))
            y = bn(p["bn2"], s["bn2"], (name, "bn2"),
                   _conv(p["conv2"], y, 1, xbar, quant_bits, products))
            if "proj" in p:
                sc = bn(p["bnp"], s["bnp"], (name, "bnp"),
                        _conv(p["proj"], h, stride, xbar, quant_bits,
                              products))
            else:
                sc = h
            h = relu(y + sc)
    h = jnp.mean(h, axis=(1, 2))
    fc = params["fc"]
    return _layer(h, fc["w"], xbar, quant_bits, products) \
        + fc["b"].astype(dtype)


def batch_statistics(params: Dict, state: Dict, x, *, xbar: int = 64):
    """Running statistics, in the layout of `state`, that every batch norm
    would measure on images x in float32: what set-up uses in place of a
    trained network's statistics, so that activations stay normalized
    through the depth."""

    def stats(p, s, x):
        measured: Dict = {}
        forward(p, s, x, xbar=xbar, measured=measured)
        return measured

    return jax.jit(stats)(params, state, x)


def logits_fn(*, xbar: int, quant_bits: Optional[int] = None,
              dtype=jnp.float32, products=None):
    """`forward` as one jitted function (params, state, x) -> float32
    logits."""
    return jax.jit(lambda p, s, x: forward(
        p, s, x, xbar=xbar, quant_bits=quant_bits, dtype=dtype,
        products=products).astype(jnp.float32))
