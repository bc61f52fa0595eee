"""Smoke run of the system's main paths on a TPU.

    python chip_smoke.py             # one chip: kernels, serve, cnn
    python chip_smoke.py --chips 4   # four chips: data-parallel training

One chip runs three phases, in order:

  * kernels - every fused Pallas kernel at serving and ResNet-18 shapes,
    compiled by Mosaic (`tpu_custom_call` in the program) and checked
    against its XLA oracle (allclose; bit-exact for q8);
  * serve   - gemma3-1b at full width through ServeEngine with the fused
    CADC matmul and paged attention; first-token and first-decode logits
    of two requests checked against an XLA-path forward;
  * cnn     - ResNet-18 (CIFAR-10, width 64) through the fused conv, fp
    (allclose) and q8 (bit-exact) against the XLA oracle.

`--chips 4` runs only the four-chip phase: three train steps of gemma3-1b
(published widths, 6 layers) on the 4-device data mesh against the same
steps on one device.

Weights and data are random, made from a fixed seed. Speeds printed here
include compilation and are not a benchmark. Exits nonzero, printing no
result, when JAX finds no TPU or any check fails; on success the last line
of stdout is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SEED = 0


class CheckFailed(Exception):
    pass


def _log(msg: str) -> None:
    print(msg, flush=True)


def _assert_kernels(name: str, lowered_text: str, want: dict) -> None:
    """The named Pallas kernels are in the program, at least `want[k]`
    times each: Mosaic compiles them, nothing fell back to XLA."""
    got = {k: lowered_text.count(f'kernel_name = "{k}"') for k in want}
    _log(f"  {name}: kernels in the program {got}")
    if any(got[k] < n for k, n in want.items()):
        raise CheckFailed(f"{name}: want kernels {want}, found {got}")


def _assert_kernel_count(name: str, compiled_text: str, at_least: int
                         ) -> None:
    """At least `at_least` Pallas kernel launches in the compiled program
    (the lowered module names each distinct kernel only once)."""
    n = compiled_text.count('custom_call_target="tpu_custom_call"')
    _log(f"  {name}: {n} kernel launches in the compiled program")
    if n < at_least:
        raise CheckFailed(f"{name}: {n} kernel launches, want >= {at_least}")


def _close(name: str, got, want, tol: float) -> float:
    """max|got - want| / max|want| <= tol, printed with the tolerance."""
    import numpy as np

    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        raise CheckFailed(f"{name}: shape {got.shape} vs {want.shape} or "
                          f"non-finite output")
    err = float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))
    _log(f"  {name}: max|diff|/max|ref| = {err:.3e} (tol {tol:.0e})")
    if not err <= tol:
        raise CheckFailed(f"{name}: error {err:.3e} > {tol:.0e}")
    return err


def _exact(name: str, got, want) -> None:
    import numpy as np

    got, want = np.asarray(got), np.asarray(want)
    n_diff = int(np.sum(got != want))
    _log(f"  {name}: {n_diff} of {want.size} elements differ (tol: "
         f"bit-exact)")
    if got.shape != want.shape or n_diff:
        raise CheckFailed(f"{name}: not bit-exact")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

# gemma3-1b CADC linears, contraction padded to the 256 crossbar:
# q, k/v, o, gate/up, down
GEMMA_LINEARS = ((1280, 1024), (1280, 256), (1024, 1152), (1280, 6912),
                 (6912, 1152))
# decode (M = slots) and prefill (M = tokens) rows
MATMUL_ROWS = (8, 4096)
# ResNet-18 (CIFAR) convs at batch CONV_BATCH: (H, Cin, Cout, k, stride)
CONV_BATCH = 128
RESNET_CONVS = ((32, 3, 64, 3, 1), (32, 64, 64, 3, 1), (32, 64, 128, 3, 2),
                (32, 64, 128, 1, 2), (16, 128, 128, 3, 1),
                (16, 128, 256, 3, 2), (16, 128, 256, 1, 2),
                (8, 256, 256, 3, 1), (8, 256, 512, 3, 2), (8, 256, 512, 1, 2),
                (4, 512, 512, 3, 1))
# serve traffic: 8 slots, 16 requests, prompts 128-512, 32-64 new tokens
SERVE_SLOTS = 8
SERVE_REQUESTS = 16
SERVE_PROMPT_LEN = (128, 512)
SERVE_MAX_NEW = (32, 64)
# ResNet-18 on CIFAR-10 at the paper's width
CNN_WIDTH = 64
CNN_BATCH = 128
# four-chip train phase
TRAIN_BATCH = 8
TRAIN_SEQ = 128
TRAIN_STEPS = 3


def phase_kernels() -> None:
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops

    key = jax.random.PRNGKey(SEED)

    def run(name, kernel, fn, *args, tol=None, exact=False, precision=None):
        f = jax.jit(fn)
        _assert_kernels(name, f.lower(*args).as_text(), {kernel: 1})
        got = f(*args)
        # fp32 oracles at "highest": the chip's default f32 matmul
        # precision is a single bf16 pass
        with jax.default_matmul_precision(precision):
            want = jax.jit(lambda *a: fn(*a, oracle=True))(*args)
        if exact:
            _exact(name, got, want)
        else:
            _close(name, got, want, tol)

    for m in MATMUL_ROWS:
        for i, (d, n) in enumerate(GEMMA_LINEARS):
            kx, kw = jax.random.split(jax.random.fold_in(key, 100 * m + i))
            x = jax.random.normal(kx, (m, d), jnp.bfloat16)
            w = (jax.random.normal(kw, (d, n), jnp.float32)
                 / d ** 0.5).astype(jnp.bfloat16)
            run(f"cadc_matmul bf16 M={m} {d}x{n}", "_kernel",
                lambda x, w, oracle=False: ops.cadc_matmul(
                    x, w, crossbar_size=256,
                    impl="xla" if oracle else "pallas").astype(jnp.float32),
                x, w, tol=1e-2)

    # gemma3-1b decode geometry: 8 slots, 4 q heads over 1 kv head,
    # head_dim 256, 16-token blocks; local (window 512) and global rings
    slots, bs = 8, 16
    for kind, ring in (("local", 512), ("global", 576)):
        nb = ring // bs
        ks = jax.random.split(jax.random.fold_in(key, 7 + ring), 4)
        q = jax.random.normal(ks[0], (slots, 1, 4, 256), jnp.bfloat16)
        kp = jax.random.normal(ks[1], (slots * nb, bs, 1, 256), jnp.bfloat16)
        vp = jax.random.normal(ks[2], (slots * nb, bs, 1, 256), jnp.bfloat16)
        table = jax.random.permutation(
            ks[3], slots * nb).reshape(slots, nb).astype(jnp.int32)
        table = table.at[-1, nb // 2:].set(-1)     # a half-allocated slot
        pos = jnp.array([0, 17, 130, 255, 300, 511, 575, 200], jnp.int32)
        attn = lambda q, kp, vp, t, p, oracle=False, kind=kind: (
            ops.paged_attention(
                q, kp, vp, t, p, kind=kind, window=512, ring_len=ring,
                impl="xla" if oracle else "pallas").astype(jnp.float32))
        run(f"paged_attention {kind} ring={ring}", "_flash_kernel", attn,
            q, kp, vp, table, pos, tol=2e-2)

    for i, (h, cin, cout, k, s) in enumerate(RESNET_CONVS):
        kx, kw = jax.random.split(jax.random.fold_in(key, 300 + i))
        shape = (CONV_BATCH, h, h, cin)
        x = jax.random.normal(kx, shape, jnp.float32)
        w = jax.random.normal(kw, (k, k, cin, cout)) / (k * k * cin) ** 0.5
        conv = lambda x, w, oracle=False, s=s: ops.cadc_conv2d(
            x, w, crossbar_size=64, stride=(s, s),
            impl="xla" if oracle else "pallas")
        run(f"cadc_conv2d fp32 {h}x{h}x{cin}->{cout} k{k} s{s}", "_kernel",
            conv, x, w, tol=1e-2, precision="highest")
        x_q = jax.random.randint(kx, shape, -7, 8, jnp.int8)
        w_c = jax.random.randint(kw, (k, k, cin, cout), -1, 2, jnp.int8)
        conv_q8 = lambda x, w, sc, oracle=False, s=s: ops.cadc_conv2d_q8(
            x, w, sc, crossbar_size=64, stride=(s, s),
            impl="xla" if oracle else "pallas")
        run(f"cadc_conv2d_q8 {h}x{h}x{cin}->{cout} k{k} s{s}", "_q8_kernel",
            conv_q8, x_q, w_c, jnp.float32(0.0371), exact=True)


def phase_serve(cfg) -> None:
    """gemma3-1b through ServeEngine as launch/serve.py builds it, with the
    fused kernels selected by cfg.kernel_impl / cfg.paged_attn_impl."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch import steps as steps_lib
    from repro.models.lm import transformer as tf
    from repro.serve import EngineConfig, ServeEngine, poisson_workload
    from repro.serve.engine import make_prefill_batch

    t0 = time.perf_counter()
    params = jax.jit(lambda k: tf.init(k, cfg))(jax.random.PRNGKey(SEED))
    jax.block_until_ready(params)
    _log(f"  params: {tf.param_count(params) / 1e9:.3f} B, init "
         f"{time.perf_counter() - t0:.1f} s")
    block = cfg.serve_block_size
    max_len = -(-(SERVE_PROMPT_LEN[1] + SERVE_MAX_NEW[1]) // block) * block
    engine = ServeEngine(cfg, params, EngineConfig(
        n_slots=SERVE_SLOTS, max_len=max_len, block_size=block, backend="paged",
        prefill_mode="batched", telemetry_every=0, record_logits=True))
    workload = poisson_workload(
        n_requests=SERVE_REQUESTS, rate=0.5, vocab_size=cfg.vocab_size,
        prompt_len=SERVE_PROMPT_LEN, max_new=SERVE_MAX_NEW, seed=SEED)
    summary = engine.run(workload)

    dev = jax.devices()[0]
    _log(f"  {dev.device_kind}: {summary['tokens_per_s']:.1f} tok/s, TTFT "
         f"p50 {summary['ttft_ms_p50']:.1f} ms, step p50 "
         f"{summary['step_ms_p50']:.1f} ms (cold: includes compilation; "
         f"not a benchmark)")
    done = [r for r in engine.results.values()
            if r.done and len(r.tokens) == r.max_new]
    if len(done) != SERVE_REQUESTS:
        raise CheckFailed(f"serve: {len(done)} of {SERVE_REQUESTS} requests "
                          f"finished")
    for r in engine.results.values():
        if not all(np.all(np.isfinite(l)) for l in r.logits):
            raise CheckFailed(f"serve: non-finite logits in request {r.rid}")
    _log(f"  {SERVE_REQUESTS} requests finished, "
         f"{sum(len(r.logits) for r in done)} logit rows finite")

    # the fused kernels are in the engine's own serving programs, lowered
    # at inputs built as the engine builds them (tables sliced to the
    # covered prefix of the longest request)
    batch, lengths, _ = make_prefill_batch(
        cfg, SERVE_SLOTS, [(0, engine.results[0])])
    _assert_kernels("serve prefill",
                    engine._prefill_fn.lower(params, batch, lengths).as_text(),
                    {"_kernel": 1})
    tables = engine._device_tables(engine.backend.covered_blocks(max_len - 1))
    zeros = jnp.zeros((SERVE_SLOTS,), jnp.int32)
    _assert_kernels("serve decode",
                    engine.backend._decode.lower(
                        params, engine.caches, tables, zeros, zeros).as_text(),
                    {"_kernel": 1, "_flash_kernel": 1})

    # first-token (prefill) and first-decode logits of two requests vs the
    # XLA-path forward over prompt + first token, same params, bf16 compute
    ref_cfg = cfg.with_overrides(kernel_impl="xla", paged_attn_impl="xla")
    reqs = [engine.results[0], engine.results[1]]
    s_pad = max(r.prompt.size for r in reqs) + 1
    toks = np.zeros((len(reqs), s_pad), np.int32)
    for i, r in enumerate(reqs):
        toks[i, : r.prompt.size] = r.prompt
        toks[i, r.prompt.size] = r.tokens[0]

    @jax.jit
    def reference(p, tokens):
        logits, _ = tf.forward_prefill(steps_lib.cast_compute(p, ref_cfg),
                                       {"tokens": tokens}, ref_cfg)
        return logits

    ref_logits = np.asarray(reference(params, jnp.asarray(toks)))
    for i, r in enumerate(reqs):
        p = r.prompt.size
        _close(f"serve req {r.rid} first-token logits (prompt {p})",
               r.logits[0], ref_logits[i, p - 1], SERVE_TOL)
        _close(f"serve req {r.rid} first-decode logits",
               r.logits[1], ref_logits[i, p], SERVE_TOL)


# bf16 compute through 26 layers: the fused path accumulates crossbar
# psums in fp32, the XLA path stores them in bf16 (bf16_wire), so the two
# differ by bf16 rounding compounded over the depth
SERVE_TOL = 2e-2


def phase_cnn() -> None:
    import jax
    import numpy as np

    from repro.core.quant import PAPER_424
    from repro.models.cnn import resnet18
    from repro.models.common import Ctx, LayerMode

    key = jax.random.PRNGKey(SEED)
    params, state = resnet18.init(key, num_classes=10, in_ch=3,
                                  width=CNN_WIDTH)
    x = jax.random.normal(jax.random.fold_in(key, 1), (CNN_BATCH, 32, 32, 3))
    # 20 convs + the classifier, each one fused kernel
    n_kernels = 1 + 2 * sum(resnet18.STAGES) + 3 + 1

    def logits(mode):
        return jax.jit(lambda p, s, x: resnet18.apply(
            p, s, x, Ctx(mode), train=False)[0])

    for name, q8 in (("fp32", False), ("q8", True)):
        kw = dict(impl="cadc", crossbar_size=64, fn="relu", q8_fused=q8,
                  quant=PAPER_424 if q8 else LayerMode().quant)
        lowered = logits(LayerMode(kernel="auto", **kw)).lower(
            params, state, x)
        _assert_kernels(f"resnet18 {name}", lowered.as_text(),
                        {"_q8_kernel" if q8 else "_kernel": 1})
        fused = lowered.compile()
        _assert_kernel_count(f"resnet18 {name}", fused.as_text(), n_kernels)
        got = np.asarray(fused(params, state, x))
        with jax.default_matmul_precision("highest"):
            want = np.asarray(logits(LayerMode(kernel="xla", **kw))(
                params, state, x))
        label = f"resnet18 width={CNN_WIDTH} batch={CNN_BATCH} {name} logits"
        if q8:
            _exact(label, got, want)
        else:
            _close(label, got, want, CNN_TOL)


# fp32 end to end through 21 layers. The oracle runs at "highest" matmul
# precision; the kernels' f32 dots run at Mosaic's default, which agrees
# with it to ~3e-3 per layer (one bf16 pass), as XLA's default does.
CNN_TOL = 2e-2


def phase_train(cfg) -> None:
    """launch.train's data-parallel path on every device vs one."""
    import gc

    import jax
    import numpy as np

    from repro.data import synthetic
    from repro.launch.train import init_train_state, make_local_mesh

    data = synthetic.make_lm_dataset(synthetic.LMTokenSpec(
        vocab_size=cfg.vocab_size, seq_len=TRAIN_SEQ))
    batches = []
    for step in range(TRAIN_STEPS):
        toks = np.asarray(data(step, TRAIN_BATCH)["tokens"])
        batches.append({"tokens": toks[:, :-1], "labels": toks[:, 1:]})

    def run(mesh):
        state = init_train_state(cfg, mesh, n_micro=1, seed=SEED)
        params, opt_state = state.params, state.opt_state
        losses = []
        with mesh:
            for step, b in enumerate(batches):
                t0 = time.perf_counter()
                params, opt_state, metrics = state.step_fn(
                    params, opt_state, jax.device_put(b, state.bshard),
                    np.int32(step))
                losses.append(float(metrics["loss"]))
                _log(f"  {mesh.devices.size} device(s) step {step}: loss "
                     f"{losses[-1]:.6f} ({time.perf_counter() - t0:.2f} s)")
        return losses

    multi = run(make_local_mesh())
    gc.collect()
    single = run(make_local_mesh(jax.devices()[:1]))
    rel = max(abs(a - b) / abs(b) for a, b in zip(multi, single))
    _log(f"  losses {len(jax.devices())} devices {multi} vs 1 device "
         f"{single}: max rel diff {rel:.3e} (tol 1e-02)")
    if not rel <= 1e-2:
        raise CheckFailed(f"train: loss mismatch {rel:.3e}")


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    from repro.launch import compile_cache

    compile_cache.enable()
    import jax

    devices = jax.devices()
    dev = devices[0]
    _log(f"device: platform={dev.platform} kind={dev.device_kind} "
         f"count={len(devices)}")
    if dev.platform != "tpu":
        print(f"no TPU found (platform {dev.platform!r})", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"--chips {args.chips} but {len(devices)} device(s)",
              file=sys.stderr)
        return 2

    from repro.configs import get_config

    if args.chips == 4:
        phases = [("train4", lambda: phase_train(get_config(
            "gemma3_1b", n_layers=6, linear_impl="cadc")))]
    else:
        phases = [
            ("kernels", phase_kernels),
            ("serve", lambda: phase_serve(get_config(
                "gemma3_1b", linear_impl="cadc", kernel_impl="auto"))),
            ("cnn", phase_cnn),
        ]
    for name, fn in phases:
        t0 = time.perf_counter()
        _log(f"phase {name}:")
        try:
            fn()
        except CheckFailed as e:
            print(f"phase {name} FAILED: {e}", file=sys.stderr)
            return 1
        _log(f"phase {name}: passed ({time.perf_counter() - t0:.1f} s)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
