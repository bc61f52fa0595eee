"""Gated short convolution: LFM2's `conv` mixer [hf:LiquidAI/LFM2-8B-A1B].

    [B; C; u] = in_proj(x)                  d -> 3d
    v = causal_depthwise_conv_L(B ⊙ u)      L = cfg.conv1d_width, no bias
    y = out_proj(C ⊙ v)

in_proj and out_proj are CADC-routable linears; the depthwise conv is
element arithmetic with no weight crossbar. A slot's decode state is the
last L - 1 rows of B ⊙ u. The batched prefill runs the whole padded wave
in parallel and hands each slot the rows before its own length, zeros
where the length is under L - 1.
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.lm import layers as ll
from repro.models.lm.xlstm import _causal_conv1d, _conv1d_step

Array = jnp.ndarray


class ConvState(NamedTuple):
    conv: Array  # [B, L - 1, d]: the last rows of B ⊙ u


def shortconv_init(key, cfg: ArchConfig) -> Dict:
    k1, k2, k3 = jax.random.split(key, 3)
    d, width = cfg.d_model, cfg.conv1d_width
    return {
        "in_proj": ll.linear_init(k1, d, 3 * d, cfg),
        "conv": {"w": jax.random.normal(k2, (width, d), jnp.float32) / width},
        "out_proj": ll.linear_init(k3, d, d, cfg),
    }


def shortconv_init_state(cfg: ArchConfig, batch: int) -> ConvState:
    return ConvState(jnp.zeros((batch, cfg.conv1d_width - 1, cfg.d_model),
                               jnp.float32))


def _gated(p: Dict, x: Array, cfg: ArchConfig) -> Tuple[Array, Array]:
    """(B ⊙ u, C) of x [..., d]."""
    b, c, u = jnp.split(ll.linear_apply(p["in_proj"], x, cfg), 3, axis=-1)
    return b * u, c


def shortconv_apply(p: Dict, x: Array, cfg: ArchConfig) -> Array:
    """x [B, S, d] -> [B, S, d]."""
    with jax.named_scope("conv"):
        bu, c = _gated(p, x, cfg)
        v = _causal_conv1d(p["conv"], bu)
        return ll.linear_apply(p["out_proj"], c * v, cfg)


def shortconv_prefill(p: Dict, x: Array, cfg: ArchConfig, lengths: Array
                      ) -> Tuple[Array, ConvState]:
    """Parallel prefill over left-aligned prompts x [B, S, d] of lengths
    [B]: (y [B, S, d], each slot's state at its own length)."""
    with jax.named_scope("conv"):
        bu, c = _gated(p, x, cfg)
        y = ll.linear_apply(p["out_proj"], c * _causal_conv1d(p["conv"], bu),
                            cfg)
        keep = cfg.conv1d_width - 1
        idx = lengths[:, None] - keep + jnp.arange(keep)[None, :]  # [B, L-1]
        rows = jnp.take_along_axis(
            bu, jnp.clip(idx, 0, x.shape[1] - 1)[..., None], axis=1)
        state = jnp.where((idx >= 0)[..., None], rows, 0)
        return y, ConvState(state.astype(jnp.float32))


def shortconv_decode(p: Dict, x: Array, cfg: ArchConfig, state: ConvState
                     ) -> Tuple[Array, ConvState]:
    """One token x [B, 1, d] -> (y [B, 1, d], state)."""
    with jax.named_scope("conv"):
        bu, c = _gated(p, x[:, 0], cfg)
        v, buf = _conv1d_step(p["conv"], state.conv.astype(bu.dtype), bu)
        y = ll.linear_apply(p["out_proj"], c * v, cfg)[:, None, :]
        return y, ConvState(buf.astype(jnp.float32))
