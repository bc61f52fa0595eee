"""Driver of the CNN cells: batch inference through the program's
forward (`models/cnn/resnet18.apply` with CADC layers on the fused
kernels), closed loop.

Set-up makes the weights and a pool of distinct input batches on the
device from the seed, each in one jitted call, and runs the pool once
through the timed program: one jitted call that runs the forward on every
pool batch in turn (`pool_forward`). The window sends that call back to
back, keeping the mix's `in_flight` calls queued on the device, and counts
the images of every call it sent, over the time until the last of them
completed. The last call's logits are kept; once the window has closed,
a sample of its batches drawn from the seed is compared with the plain
reference, image by image, by the numbers the mode's limits name
(`readings`).
"""
from __future__ import annotations

import collections
import time
from typing import Any, Deque, Dict

import numpy as np

import session
import trace_reduce
from traffic import generator


def make_weights(cfg: Dict, seed: int, margin: float = 0.0):
    """(params, state) of the program's ResNet-18 layout, from the seed,
    in one jitted call: He-normal convs and classifier, batch-norm scale
    and bias near identity, and running statistics that the reference
    measures on a calibration batch drawn from the seed. With `margin`,
    every conv and classifier weight's |w| is kept that share away from
    its tensor's ternary threshold (`away_from_threshold`)."""
    import jax
    import jax.numpy as jnp

    w, classes, in_ch = cfg["width"], cfg["num_classes"], cfg["in_ch"]

    def conv(k, kh, cin, cout):
        return {"w": away_from_threshold(
            jax.random.normal(k, (kh, kh, cin, cout))
            * jnp.sqrt(2.0 / (kh * kh * cin)), margin)}

    def bn(k, c):
        k1, k2 = jax.random.split(k)
        p = {"scale": 1.0 + 0.1 * jax.random.normal(k1, (c,)),
             "bias": 0.1 * jax.random.normal(k2, (c,))}
        s = {"mean": jnp.zeros((c,)), "var": jnp.ones((c,))}
        return p, s

    def make(key):
        keys = iter(jax.random.split(key, 64))
        params = {"stem": conv(next(keys), 3, in_ch, w)}
        params["bn_stem"], st = bn(next(keys), w)
        state = {"bn_stem": st}
        cin = w
        for si, n_blocks in enumerate(cfg["stages"]):
            cout = w * 2 ** si
            for bi in range(n_blocks):
                stride = 2 if (si > 0 and bi == 0) else 1
                p = {"conv1": conv(next(keys), 3, cin, cout),
                     "conv2": conv(next(keys), 3, cout, cout)}
                s = {}
                p["bn1"], s["bn1"] = bn(next(keys), cout)
                p["bn2"], s["bn2"] = bn(next(keys), cout)
                if stride != 1 or cin != cout:
                    p["proj"] = conv(next(keys), 1, cin, cout)
                    p["bnp"], s["bnp"] = bn(next(keys), cout)
                params[f"s{si}b{bi}"], state[f"s{si}b{bi}"] = p, s
                cin = cout
        kf, kb = jax.random.split(next(keys))
        params["fc"] = {"w": away_from_threshold(
                            jax.random.normal(kf, (cin, classes))
                            * jnp.sqrt(2.0 / cin), margin),
                        "b": 0.1 * jax.random.normal(kb, (classes,))}
        return params, state

    params, state = jax.jit(make)(session.key(seed))
    calib = jax.random.normal(jax.random.fold_in(session.key(seed), 2),
                              (cfg["calibration_images"], cfg["image_hw"],
                               cfg["image_hw"], in_ch))
    from reference import resnet18_cifar as ref

    return params, ref.batch_statistics(params, state, calib,
                                        xbar=cfg["crossbar_size"])


def away_from_threshold(w, margin: float):
    """`w` with every |w| within `margin` (a share) of the ternary rule's
    threshold 0.7 mean|w| moved to the band's nearer edge, sign kept. A
    weight that close to the threshold gets its code from float rounding:
    the program's and the reference's means of |w| differ in the last
    bits, and a code on the other side changes every image's answer. The
    move shifts the mean, and so the threshold, by about margin**2."""
    import jax.numpy as jnp

    if not margin:
        return w
    a = jnp.abs(w)
    delta = 0.7 * jnp.mean(a)
    lo, hi = delta * (1 - margin), delta * (1 + margin)
    a = jnp.where((a > lo) & (a < hi), jnp.where(a < delta, lo, hi), a)
    return jnp.sign(w) * a


def pool_forward(lm, pool_n: int):
    """(params, state, xs [pool_n, batch, ...]) -> logits [pool_n, batch,
    classes]: the program's forward on each pool batch in turn, in
    one device loop. Parameters and state pass an optimization barrier
    every turn, so nothing that depends on them alone (the weights'
    ternary codes, a cast) is hoisted out of the loop: each turn does one
    whole forward's work."""
    import jax
    import jax.numpy as jnp

    from repro.models.cnn import resnet18
    from repro.models.common import Ctx

    def one(p, s, x):
        return resnet18.apply(p, s, x, Ctx(lm), train=False)[0]

    def run(p, s, xs):
        shape = jax.eval_shape(one, p, s, xs[0]).shape
        out = jnp.zeros((pool_n,) + shape, jnp.float32)

        def body(i, carry):
            p, s, out = carry
            y = one(p, s, jax.lax.dynamic_index_in_dim(xs, i, keepdims=False))
            out = jax.lax.dynamic_update_index_in_dim(
                out, y.astype(jnp.float32), i, 0)
            p, s = jax.lax.optimization_barrier((p, s))
            return p, s, out

        return jax.lax.fori_loop(0, pool_n, body, (p, s, out))[2]

    return run


def layer_mode(cfg: Dict, mode: Dict):
    from repro.core.quant import FP32, QuantConfig
    from repro.models.common import LayerMode

    quant = QuantConfig(**mode["quant"]) if mode["quant"] else FP32
    return LayerMode(impl="cadc", crossbar_size=cfg["crossbar_size"],
                     fn=cfg["dendritic_fn"], kernel=cfg["kernel"],
                     q8_fused=mode["q8_fused"], quant=quant)


def same_layout(got, want) -> None:
    """Raise unless two pytrees have one structure, shapes and dtypes."""
    import jax

    g = jax.tree_util.tree_structure(got)
    w = jax.tree_util.tree_structure(want)
    if g != w:
        raise ValueError(f"weights do not match the program's layout:\n{g}\n"
                         f"vs\n{w}")
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        if a.shape != b.shape or a.dtype != b.dtype:
            raise ValueError(f"leaf {a.shape} {a.dtype} vs {b.shape} "
                             f"{b.dtype}")


def run(ctx) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp

    from repro.models.cnn import resnet18

    from reference import resnet18_cifar as ref

    cfg, mix = ctx.config, ctx.mix
    mode = cfg["modes"][mix["mode"]]
    batch, pool_n = mix["batch"], mix["pool"]
    hw = cfg["image_hw"]

    params, state = make_weights(cfg, ctx.seed,
                                 mode.get("ternary_margin", 0.0))
    same_layout((params, state), jax.eval_shape(
        lambda k: resnet18.init(k, num_classes=cfg["num_classes"],
                                in_ch=cfg["in_ch"], width=cfg["width"]),
        jax.random.PRNGKey(0)))
    lm = layer_mode(cfg, mode)
    make_batch = jax.jit(lambda k: jax.random.normal(
        k, (batch, hw, hw, cfg["in_ch"])))
    data_key = jax.random.fold_in(session.key(ctx.seed), 1)
    pool = [make_batch(jax.random.fold_in(data_key, i))
            for i in range(pool_n)]
    xs = jnp.stack(pool)
    fwd = jax.jit(pool_forward(lm, pool_n))
    fwd(params, state, xs).block_until_ready()
    setup_s = time.perf_counter() - ctx.t_start
    ctx.log(f"set-up {setup_s:.3f} s: {pool_n} batches of {batch}")

    # window: each call runs the forward over the whole pool, and the mix's
    # `in_flight` calls (some seconds of forwards) stay queued on the
    # device, so a host stall shorter than their run leaves the device
    # busy. Once the time is up nothing more is sent, and the window closes
    # when every call sent has completed: all of them count, over all of
    # that time.
    depth = mix["in_flight"]
    queue: Deque = collections.deque()  # logits [pool, batch, classes]
    last = None
    prof = session.Profiler() if ctx.trace else None
    t_a, t_b = session.trace_span(ctx.seconds)
    tracing = None          # None: not yet, True: on, False: done
    traced: Dict[str, Any] = {}
    n_compiles = ctx.compiles()
    n_sent = 0

    def drain():
        nonlocal last
        for y in queue:
            y.block_until_ready()
            last = y

    def stop_trace():
        drain()
        annot.__exit__(None, None, None)
        traced["batches"] = (n_sent - traced_from) * pool_n
        traced["events"] = prof.stop()

    t0 = time.perf_counter()
    while True:
        now = time.perf_counter() - t0
        if now >= ctx.seconds:
            break
        if prof is not None and tracing is None and now >= t_a:
            drain()
            prof.start()
            annot = jax.profiler.TraceAnnotation("bench.window")
            annot.__enter__()
            traced_from, tracing = n_sent, True
        if tracing and now >= t_b:
            stop_trace()
            tracing = False
        while len(queue) < depth:
            queue.append(fwd(params, state, xs))
            n_sent += 1
        last = queue.popleft()
        last.block_until_ready()
    t_stop = time.perf_counter()
    if tracing:
        stop_trace()
    drain()
    window_s = time.perf_counter() - t0
    ctx.log(f"window {window_s:.3f} s, {time.perf_counter() - t_stop:.3f} s "
            f"of it after the last send, for the {len(queue)} calls still "
            f"queued")
    n_compiles = ctx.compiles() - n_compiles
    mem = session.memory_peak_bytes()

    rec: Dict[str, Any] = {
        "kind": "cnn", "mode": mix["mode"], "seconds": ctx.seconds,
        "window_s": window_s, "setup_s": setup_s,
        "images": n_sent * pool_n * batch, "batch": batch,
        "attempted": n_sent * pool_n, "failed": 0,
        "compiles_in_window": n_compiles, "memory_peak_bytes": mem,
        "config": cfg, "mix": mix,
    }
    if prof is not None:
        rec["trace"] = reduce_trace(traced["events"], traced["batches"])

    # correctness: a sample of the last call's batches against the reference
    pick = generator.rng(ctx.seed, 7).choice(
        pool_n, size=min(mix["check_batches"], pool_n), replace=False)
    got_all = np.asarray(last, np.float32)
    ref_fn = ref.logits_fn(**reference_args(cfg, mode))
    ctl_fn = ref.logits_fn(**control_args(cfg, mode)) if ctx.control else None
    pairs, ctl_pairs = [], []  # (got, want) of each checked batch
    for j in pick:
        want = np.asarray(ref_fn(params, state, pool[j]))
        pairs.append((got_all[j], want))
        if ctl_fn is not None:
            ctl_pairs.append((np.asarray(ctl_fn(params, state, pool[j])),
                              want))
    ctx.log(f"checked {len(pick)} of the window's batches "
            f"({len(pick) * batch} images) against the reference")
    rec["readings"] = readings(pairs)
    rec["checks"] = {k: session.check(rec["readings"][k], limit)
                     for k, limit in cfg["limits"][mix["mode"]].items()}
    if ctl_fn is not None:
        rec["control"] = readings(ctl_pairs)
    return rec


def reference_args(cfg: Dict, mode: Dict) -> Dict:
    """The reference's arithmetic for a mode: q8 at its activation bits,
    or float32 with products rounded as this platform's default matmul
    precision rounds them (bfloat16 operands on a TPU)."""
    import jax
    import jax.numpy as jnp

    args = {"xbar": cfg["crossbar_size"]}
    if mode["quant"]:
        args["quant_bits"] = mode["quant"]["input_bits"]
    elif jax.default_backend() == "tpu":
        args["products"] = jnp.dtype(mode["tpu_products"])
    return args


def control_args(cfg: Dict, mode: Dict) -> Dict:
    """The reference's arithmetic one step below what a mode states: for
    q8, activation codes one bit shorter; for float32, every value in
    bfloat16."""
    import jax.numpy as jnp

    if mode["quant"]:
        return {"xbar": cfg["crossbar_size"],
                "quant_bits": mode["quant"]["input_bits"] - 1}
    return {"xbar": cfg["crossbar_size"], "dtype": jnp.bfloat16}


def image_errors(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    """Largest |got - want| of each image's logits, relative to the
    largest |want| of the batch; every image reads inf where the shapes
    differ or a value is not finite."""
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        return np.full(want.shape[0], np.inf)
    return np.max(np.abs(got - want), axis=1) / max(np.max(np.abs(want)),
                                                    1e-30)


def top1_off(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    """Per image, whether `got` ranks another class first than the
    reference does; every image where the shapes differ or a value is not
    finite."""
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        return np.ones(want.shape[0], bool)
    return np.argmax(got, axis=1) != np.argmax(want, axis=1)


def logit_error(got: np.ndarray, want: np.ndarray) -> float:
    """Largest |got - want| over a batch's logits, relative to the largest
    |want|; a shape mismatch or a value that is not finite reads inf."""
    return float(np.max(image_errors(got, want)))


def readings(pairs) -> Dict[str, float]:
    """The numbers a mode's limits can name, over the checked batches'
    (got, want) logits: the largest per-image error (`logit_err`) and the
    share of images whose first class differs from the reference's, in
    percent (`top1_off_pct`)."""
    errs = np.concatenate([image_errors(g, w) for g, w in pairs])
    off = np.concatenate([top1_off(g, w) for g, w in pairs])
    return {"logit_err": float(np.max(errs)),
            "top1_off_pct": 100.0 * float(np.mean(off))}


def reduce_trace(events: Dict, n_batches: int) -> Dict[str, Any]:
    """The traced window (the benchmark's `bench.window` span) of
    `n_batches` whole forwards. The device is drained before the profiler
    starts and before it stops, so every device event of the session
    belongs to one of those forwards: the conv readers count calls over
    the session, which an event at the window's edge cannot leave out."""
    t = trace_reduce.window(events,
                            trace_reduce.spans(events["host"], "bench.window"))
    t["batches"] = n_batches
    return t
