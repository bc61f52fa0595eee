"""ResNet-18, CIFAR variant (paper benchmark #2).

Stem 3x3/64 (no maxpool), stages [2,2,2,2] BasicBlocks at 64/128/256/512,
global-avg-pool, FC. `width` scales channels for CI-speed reduced configs.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models import common as cm

STAGES = (2, 2, 2, 2)


def _block_init(key, cin, cout, stride):
    k = jax.random.split(key, 3)
    p = {
        "conv1": cm.conv_init(k[0], 3, 3, cin, cout),
        "conv2": cm.conv_init(k[1], 3, 3, cout, cout),
    }
    bn1p, bn1s = cm.bn_init(cout)
    bn2p, bn2s = cm.bn_init(cout)
    p["bn1"], p["bn2"] = bn1p, bn2p
    s = {"bn1": bn1s, "bn2": bn2s}
    if stride != 1 or cin != cout:
        p["proj"] = cm.conv_init(k[2], 1, 1, cin, cout)
        bnp, bns = cm.bn_init(cout)
        p["bnp"], s["bnp"] = bnp, bns
    return p, s


def init(key, *, num_classes: int = 10, in_ch: int = 3, width: int = 64):
    keys = jax.random.split(key, 16)
    chans = [width, width * 2, width * 4, width * 8]
    params: Dict[str, Any] = {"stem": cm.conv_init(keys[0], 3, 3, in_ch, width)}
    bnp, bns = cm.bn_init(width)
    params["bn_stem"] = bnp
    state: Dict[str, Any] = {"bn_stem": bns}
    cin = width
    ki = 1
    for si, (n_blocks, cout) in enumerate(zip(STAGES, chans)):
        for bi in range(n_blocks):
            stride = 2 if (si > 0 and bi == 0) else 1
            bp, bs = _block_init(keys[ki], cin, cout, stride)
            params[f"s{si}b{bi}"] = bp
            state[f"s{si}b{bi}"] = bs
            cin = cout
            ki += 1
    params["fc"] = cm.dense_init(keys[ki], cin, num_classes)
    return params, state


def _block_apply(p, s, x, ctx, *, stride, train, name):
    ns = {}
    h = cm.conv_forward(p["conv1"], x, ctx, stride=(stride, stride), name=f"{name}.conv1")
    h, ns["bn1"] = cm.bn_forward(p["bn1"], s["bn1"], h, train=train,
                                 name="bn1")
    with jax.named_scope("relu"):
        h = jax.nn.relu(h)
    h = cm.conv_forward(p["conv2"], h, ctx, name=f"{name}.conv2")
    h, ns["bn2"] = cm.bn_forward(p["bn2"], s["bn2"], h, train=train,
                                 name="bn2")
    if "proj" in p:
        sc = cm.conv_forward(p["proj"], x, ctx, stride=(stride, stride),
                             name=f"{name}.proj")
        sc, ns["bnp"] = cm.bn_forward(p["bnp"], s["bnp"], sc, train=train,
                                      name="bnp")
    else:
        sc = x
    with jax.named_scope("residual"):
        return jax.nn.relu(h + sc), ns


def apply(params, state, x, ctx: cm.Ctx, *, train: bool = False):
    """Logits and the new BN state. Each part of the forward runs under a
    named scope (`stem`, `s{stage}b{block}`, `head`; inside them the
    layer, `bn*`, `relu` and `residual`), which the compiled program
    carries as op-name metadata and nothing else."""
    new_state: Dict[str, Any] = {}
    with jax.named_scope("stem"):
        h = cm.conv_forward(params["stem"], x, ctx, name="stem")
        h, new_state["bn_stem"] = cm.bn_forward(
            params["bn_stem"], state["bn_stem"], h, train=train
        )
        with jax.named_scope("relu"):
            h = jax.nn.relu(h)
    for si, n_blocks in enumerate(STAGES):
        for bi in range(n_blocks):
            stride = 2 if (si > 0 and bi == 0) else 1
            name = f"s{si}b{bi}"
            with jax.named_scope(name):
                h, new_state[name] = _block_apply(
                    params[name], state[name], h, ctx,
                    stride=stride, train=train, name=name,
                )
    with jax.named_scope("head"):
        h = cm.global_avg_pool(h)
        logits = cm.linear_forward(params["fc"], h, ctx, name="fc")
    return logits, new_state
