"""Production LM training driver.

    PYTHONPATH=src python -m repro.launch.train --arch gemma_7b --smoke \
        --steps 20 --batch 8 --seq 128 --ckpt-dir /tmp/run1

Runs the SAME train_step the multi-pod dry-run compiles, on whatever mesh
the process sees: the full (data, model) production mesh on a pod, or an
automatic (n_devices,)-shaped data mesh locally. Fault tolerance:

  * step-atomic checkpoints (write-tmp -> fsync -> rename) every
    --ckpt-every steps, keep-k GC; restart resumes from the latest COMPLETE
    checkpoint (a killed run never leaves a half-written restore target).
  * the data pipeline is stateless-seeded by step => bit-exact restarts.
  * elastic rescale: the checkpoint stores unsharded leaves by name; on
    restore the sharding rules re-lay params for the CURRENT mesh, so a
    512-chip checkpoint restores on 8 chips (or 1 CPU) unchanged.
  * straggler/hang mitigation at scale: per-step wall-clock watchdog
    (--step-timeout) — on expiry the launcher exits nonzero so the cluster
    scheduler restarts the job from the last checkpoint.

Overlap/perf knobs (documented for real-TPU runs; no-ops on CPU):
  * XLA_FLAGS=--xla_tpu_enable_latency_hiding_scheduler=true overlaps the
    FSDP all-gathers/reduce-scatters with compute under scan-over-layers.
  * --microbatch N trades memory for per-step collective amortization
    (grad accumulation inside one jit region; PP-ready interface).
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import time
from typing import Any, Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.ckpt import checkpoint as ckpt
from repro.configs import SHAPES, get_config, smoke_config
from repro.data import synthetic
from repro.launch import compile_cache
from repro.launch import mesh as mesh_lib
from repro.launch import steps as steps_lib
from repro.parallel import sharding as shard_lib


def make_local_mesh(devices=None) -> jax.sharding.Mesh:
    """(n, 1) data mesh over `devices` (default: every visible device)."""
    devices = jax.devices() if devices is None else list(devices)
    return mesh_lib.make_mesh((len(devices), 1), ("data", "model"),
                              devices=devices)


class TrainState(NamedTuple):
    params: Any
    opt_state: Any
    step_fn: Callable   # (params, opt_state, batch, step) -> (..., metrics)
    pshard: Any         # NamedSharding pytrees on the mesh
    oshard: Any
    bshard: Any


def init_train_state(cfg, mesh: jax.sharding.Mesh, *, n_micro: int = 1,
                     seed: int = 0) -> TrainState:
    """Params and optimizer state initialized under the mesh's sharding
    rules, and the jitted train step. The step's outputs are pinned to the
    same shardings as its inputs, so step t+1 reuses step t's executable
    (left to propagation, the optimizer state came back re-laid and the
    second step compiled again)."""
    optimizer = steps_lib.make_optimizer(cfg)
    train_step = steps_lib.make_train_step(cfg, optimizer, n_micro=n_micro)
    params_shape = steps_lib.abstract_params(cfg)
    pshard = shard_lib.to_named(
        shard_lib.param_specs(params_shape, cfg, mesh), mesh)
    # optimizer state = param-shaped moment trees ({'m', 'v'} / {'mom'})
    oshard = {k: pshard
              for k in jax.eval_shape(optimizer.init, params_shape)}
    bshard = shard_lib.to_named(shard_lib.batch_specs(cfg, mesh, "train"),
                                mesh)
    with mesh:
        params = jax.jit(lambda k: steps_lib.tf.init(k, cfg),
                         out_shardings=pshard)(jax.random.PRNGKey(seed))
        opt_state = jax.jit(optimizer.init, out_shardings=oshard)(params)
    step_fn = jax.jit(train_step, donate_argnums=(0, 1),
                      out_shardings=(pshard, oshard, None))
    return TrainState(params, opt_state, step_fn, pshard, oshard, bshard)


class StepWatchdog:
    """SIGALRM-based per-step timeout: straggler/hang mitigation for
    synchronous training — exit nonzero, let the scheduler restart from
    the last checkpoint."""

    def __init__(self, timeout_s: Optional[float]):
        self.timeout_s = timeout_s

    def __enter__(self):
        if self.timeout_s:
            def on_timeout(signum, frame):
                raise TimeoutError(
                    f"step exceeded {self.timeout_s}s — likely straggler/hang; "
                    "exiting for scheduler restart"
                )
            signal.signal(signal.SIGALRM, on_timeout)
            signal.setitimer(signal.ITIMER_REAL, self.timeout_s)
        return self

    def __exit__(self, *exc):
        if self.timeout_s:
            signal.setitimer(signal.ITIMER_REAL, 0)
        return False


def main(argv=None) -> Dict[str, Any]:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-trainable)")
    ap.add_argument("--cadc", action="store_true",
                    help="enable the paper's technique on every matmul")
    ap.add_argument("--crossbar", type=int, default=256)
    ap.add_argument("--fn", default="relu")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--microbatch", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--keep-k", type=int, default=3)
    ap.add_argument("--step-timeout", type=float, default=None)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--production-mesh", action="store_true",
                    help="use the 16x16 pod mesh (needs 256 devices)")
    args = ap.parse_args(argv)
    compile_cache.enable()

    cfg = (smoke_config if args.smoke else get_config)(args.arch)
    cfg = cfg.with_overrides(n_microbatches=args.microbatch)
    if args.cadc:
        cfg = cfg.with_overrides(linear_impl="cadc",
                                 crossbar_size=args.crossbar,
                                 dendritic_fn=args.fn)

    mesh = (mesh_lib.make_production_mesh() if args.production_mesh
            else make_local_mesh())
    print(f"mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))} "
          f"arch={cfg.name} cadc={args.cadc} params=...", flush=True)

    state = init_train_state(cfg, mesh, n_micro=args.microbatch)
    params, opt_state = state.params, state.opt_state
    n_params = sum(int(np.prod(x.shape))
                   for x in jax.tree_util.tree_leaves(params))
    print(f"params: {n_params/1e6:.1f}M", flush=True)

    start_step = 0
    if args.ckpt_dir and ckpt.latest_step(args.ckpt_dir) is not None:
        start_step, tree = ckpt.restore(
            args.ckpt_dir, {"params": params, "opt": opt_state}
        )
        # elastic re-lay onto the current mesh
        with mesh:
            params, opt_state = jax.jit(
                lambda x: x, out_shardings=(state.pshard, state.oshard)
            )((tree["params"], tree["opt"]))
        print(f"restored step {start_step} from {args.ckpt_dir}", flush=True)

    data = synthetic.make_lm_dataset(synthetic.LMTokenSpec(
        vocab_size=cfg.vocab_size, seq_len=args.seq))
    bshard, step_fn = state.bshard, state.step_fn
    history = []
    with mesh:
        for step in range(start_step, args.steps):
            raw = data(step, args.batch)
            toks = raw["tokens"]
            batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
            if cfg.frontend == "vit":
                batch["patches"] = jnp.zeros(
                    (args.batch, cfg.frontend_len, cfg.frontend_dim),
                    jnp.float32)
            if cfg.frontend == "audio":
                batch = {"frames": jnp.zeros(
                    (args.batch, args.seq, cfg.frontend_dim), jnp.float32),
                    "labels": toks[:, 1:]}
            batch = jax.device_put(batch, bshard)

            t0 = time.time()
            with StepWatchdog(args.step_timeout):
                params, opt_state, metrics = step_fn(
                    params, opt_state, batch, jnp.asarray(step, jnp.int32)
                )
                loss = float(metrics["loss"])
            dt = time.time() - t0

            if step % args.log_every == 0 or step == args.steps - 1:
                print(f"step {step:5d}  loss {loss:8.4f}  {dt*1e3:7.1f} ms",
                      flush=True)
                history.append({"step": step, "loss": loss, "s": dt})
            if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
                fn = ckpt.save(args.ckpt_dir, step + 1,
                               {"params": params, "opt": opt_state},
                               keep_k=args.keep_k)
                print(f"ckpt -> {fn}", flush=True)

    if history:
        first, last = history[0]["loss"], history[-1]["loss"]
        print(f"loss {first:.4f} -> {last:.4f} "
              f"({'improved' if last < first else 'NOT improved'})", flush=True)
    return {"history": history, "params": params}


if __name__ == "__main__":
    main()
