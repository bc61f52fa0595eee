"""jit-able step functions + abstract input specs for every (arch x shape).

train_step: microbatched grad accumulation (lax.scan) -> AdamW update.
prefill_step: full-sequence forward, last-position logits.
serve_step (decode): one token through the KV/recurrent caches.

input_specs() returns ShapeDtypeStructs (weak-type-correct, shardable, no
allocation) — the dry-run lowers against these.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, SHAPES, ShapeConfig
from repro.models.lm import transformer as tf
from repro.train import optimizer as opt_lib

Array = jnp.ndarray


# ---------------------------------------------------------------------------
# abstract inputs
# ---------------------------------------------------------------------------

def input_specs(cfg: ArchConfig, shape: ShapeConfig) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for the step inputs (batch only)."""
    b, s = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    f32 = jnp.float32
    if shape.kind == "train":
        if cfg.frontend == "audio":
            return {
                "frames": jax.ShapeDtypeStruct((b, s, cfg.frontend_dim), f32),
                "labels": jax.ShapeDtypeStruct((b, s), i32),
            }
        out = {
            "tokens": jax.ShapeDtypeStruct((b, s), i32),
            "labels": jax.ShapeDtypeStruct((b, s), i32),
        }
        if cfg.frontend == "vit":
            out["patches"] = jax.ShapeDtypeStruct(
                (b, cfg.frontend_len, cfg.frontend_dim), f32
            )
        return out
    if shape.kind == "prefill":
        if cfg.frontend == "audio":
            return {"frames": jax.ShapeDtypeStruct((b, s, cfg.frontend_dim), f32)}
        out = {"tokens": jax.ShapeDtypeStruct((b, s), i32)}
        if cfg.frontend == "vit":
            out["patches"] = jax.ShapeDtypeStruct(
                (b, cfg.frontend_len, cfg.frontend_dim), f32
            )
        return out
    # decode: one new token, caches sized at shape.seq_len
    return {
        "tokens": jax.ShapeDtypeStruct((b,), i32),
        "position": jax.ShapeDtypeStruct((), i32),
    }


def abstract_params(cfg: ArchConfig):
    shapes = jax.eval_shape(lambda k: tf.init(k, cfg), jax.random.PRNGKey(0))
    if cfg.params_dtype != "float32":
        dt = jnp.dtype(cfg.params_dtype)

        def recast(s):
            if jnp.issubdtype(s.dtype, jnp.floating):
                return jax.ShapeDtypeStruct(s.shape, dt)
            return s

        shapes = jax.tree_util.tree_map(recast, shapes)
    return shapes


def abstract_caches(cfg: ArchConfig, batch: int, seq_len: int):
    return jax.eval_shape(
        lambda: tf.init_caches(cfg, batch, seq_len)
    )


def abstract_opt_state(optimizer: opt_lib.Optimizer, params_shape):
    return jax.eval_shape(optimizer.init, params_shape)


# ---------------------------------------------------------------------------
# steps
# ---------------------------------------------------------------------------

def make_optimizer(cfg: ArchConfig) -> opt_lib.Optimizer:
    return opt_lib.adamw(
        lr=opt_lib.cosine_warmup_schedule(3e-4, 2000, 100_000),
        weight_decay=0.1,
        max_grad_norm=1.0,
    )


def cast_compute(params, cfg: ArchConfig):
    """bf16_wire (§Perf iter 2): one shard-local cast of the fp32 master
    params to the compute dtype at the top of the step. Every FSDP
    all-gather then moves bf16 (half the bytes), and the wgrad reductions
    — cotangents of the bf16 copies — ride bf16 too; the optimizer applies
    the (f32-converted) grads to the fp32 masters as usual."""
    if not cfg.bf16_wire:
        return params
    dt = jnp.dtype(cfg.dtype)

    def cast(a):
        return a.astype(dt) if a.dtype == jnp.float32 else a

    return jax.tree_util.tree_map(cast, params)


def make_train_step(cfg: ArchConfig, optimizer: Optional[opt_lib.Optimizer] = None,
                    n_micro: Optional[int] = None) -> Callable:
    optimizer = optimizer or make_optimizer(cfg)
    n_micro = n_micro or cfg.n_microbatches

    def loss_fn(params, micro_batch):
        logits, aux = tf.forward_train(cast_compute(params, cfg), micro_batch,
                                       cfg)
        loss, metrics = tf.lm_loss(logits, micro_batch["labels"])
        return loss + 0.01 * aux, metrics

    def train_step(params, opt_state, batch, step):
        def micro(i, b):  # slice microbatch i out of the global batch
            return jax.tree_util.tree_map(
                lambda a: a.reshape(n_micro, -1, *a.shape[1:])[i], b
            )

        def accum(carry, i):
            gsum, lsum = carry
            (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, micro(i, batch)
            )
            gsum = jax.tree_util.tree_map(
                lambda a, g: a + g.astype(jnp.float32), gsum, grads
            )
            return (gsum, lsum + loss), None

        gzero = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params
        )
        (gsum, lsum), _ = jax.lax.scan(
            accum, (gzero, jnp.zeros((), jnp.float32)), jnp.arange(n_micro)
        )
        grads = jax.tree_util.tree_map(lambda g: g / n_micro, gsum)
        updates, opt_state = optimizer.update(grads, opt_state, params, step)
        params = opt_lib.apply_updates(params, updates)
        return params, opt_state, {"loss": lsum / n_micro}

    return train_step


def make_prefill_step(cfg: ArchConfig) -> Callable:
    def prefill_step(params, batch):
        logits, _ = tf.forward_train(cast_compute(params, cfg), batch, cfg)
        return logits[:, -1, :]  # next-token logits

    return prefill_step


def make_batched_prefill_step(cfg: ArchConfig) -> Callable:
    """Serving prefill: full-sequence forward over left-aligned ragged
    prompts. lengths [B] picks each slot's own last-token logits (per-slot
    position offsets for the subsequent decode steps = lengths). Returns
    (next_tokens [B], last_logits [B, V], cache contributions, routed) —
    the contributions feed the serve engine's cache writers (dense or
    paged); `routed` is each MoE layer's routing, the rows routed to each
    held expert and every row's selected experts (`transformer.
    _routed_rows`; () without experts)."""

    def batched_prefill_step(params, batch, lengths):
        logits, contribs, routed = tf.forward_prefill(
            cast_compute(params, cfg), batch, cfg, lengths=lengths,
            with_routed=True)
        idx = jnp.maximum(lengths - 1, 0)[:, None, None]
        last = jnp.take_along_axis(logits, idx, axis=1)[:, 0]
        return (jnp.argmax(last, -1).astype(jnp.int32), last, contribs,
                routed)

    return batched_prefill_step


def make_serve_step(cfg: ArchConfig) -> Callable:
    """One fused decode step. `position` may be a scalar (legacy fixed
    batch) or a [B] vector of per-slot offsets (continuous batching)."""

    def serve_step(params, tokens, position, caches):
        logits, caches = tf.decode_step(cast_compute(params, cfg), tokens,
                                        position, caches, cfg)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), logits, caches

    return serve_step
