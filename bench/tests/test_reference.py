"""The plain references against the program, at smoke size on the CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import smoke
from drivers import cnn as cnn_drv
from drivers import serve as serve_drv
from reference import phi4_decoder, resnet18_cifar


@pytest.mark.parametrize("mode,kernel", [("fp32", "xla"), ("q8", "xla"),
                                         ("fp32", "interpret")])
def test_resnet18_reference_matches_the_program(mode, kernel):
    from repro.models.cnn import resnet18
    from repro.models.common import Ctx

    cfg = smoke.resnet(kernel=kernel)
    m = cfg["modes"][mode]
    params, state = cnn_drv.make_weights(cfg, 2**31 + 9,
                                         m.get("ternary_margin", 0.0))
    x = jax.random.normal(jax.random.PRNGKey(3), (4, 32, 32, 3))
    got = resnet18.apply(params, state, x, Ctx(cnn_drv.layer_mode(cfg, m)),
                         train=False)[0]
    want = resnet18_cifar.logits_fn(**cnn_drv.reference_args(cfg, m))(
        params, state, x)
    err = cnn_drv.logit_error(np.asarray(got), np.asarray(want))
    # float32 on the CPU: summation order only; q8: integer arithmetic
    assert err < (1e-5 if mode == "fp32" else 1e-6), err


def test_q8_weights_stay_off_the_ternary_threshold():
    """No |w| lies within half the margin of its tensor's ternary threshold
    after the move, and only weights inside the band move."""
    margin = 1e-3
    w = jax.random.normal(jax.random.PRNGKey(6), (3, 3, 64, 64))
    v = np.asarray(cnn_drv.away_from_threshold(w, margin))
    w = np.asarray(w)
    a = np.abs(v)
    delta = 0.7 * a.mean()
    assert np.min(np.abs(a - delta)) > 0.5 * margin * delta
    moved = v != w
    assert 0 < moved.sum() < 1e-2 * w.size
    assert np.all(np.sign(v) == np.sign(w))
    assert np.max(np.abs(v - w)) <= margin * delta * 1.01


def test_resnet18_reference_dendritic_gate_matters():
    """Without the per-crossbar ReLU the logits move: the reference
    really computes the CADC sum, not a plain conv."""
    cfg = smoke.resnet()
    params, state = cnn_drv.make_weights(cfg, 5)
    x = jax.random.normal(jax.random.PRNGKey(4), (4, 32, 32, 3))
    want = resnet18_cifar.logits_fn(xbar=64)(params, state, x)
    plain = resnet18_cifar.logits_fn(xbar=10**6)(params, state, x)
    assert cnn_drv.logit_error(np.asarray(plain), np.asarray(want)) > 1e-2


def test_phi4_reference_matches_engine_prefill_and_decode():
    """Prefill logits and decode logits read back through the paged cache,
    in float32, against the reference's full forward."""
    from repro.serve import EngineConfig, ServeEngine

    cfg = smoke.phi4(dtype="float32")
    arch = serve_drv.program_config(cfg)
    params = serve_drv.make_weights(arch, cfg, 11)
    eng = ServeEngine(arch, params, EngineConfig(
        n_slots=4, max_len=64, block_size=16, backend="paged",
        prefill_mode="batched", telemetry_every=0, record_logits=True))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg["vocab_size"], n) for n in (5, 17, 30)]
    for p in prompts:
        eng.submit(p, 12)
    while eng.has_work():
        eng.step()
    ref = phi4_decoder.logits_fn(cfg)
    for rid, p in enumerate(prompts):
        req = eng.results[rid]
        seq = np.concatenate([p, req.tokens]).astype(np.int32)
        want = np.asarray(ref(params, jnp.asarray(seq)))
        got = np.stack(req.logits)
        rows = want[p.size - 1: p.size - 1 + len(req.tokens)]
        np.testing.assert_allclose(got, rows, atol=2e-4, rtol=0)
        assert serve_drv.widest_gap(want, p.size, req.tokens) == 0.0


def test_phi4_reference_fp8_control_differs():
    cfg = smoke.phi4()
    arch = serve_drv.program_config(cfg)
    params = serve_drv.make_weights(arch, cfg, 12)
    toks = jnp.asarray(np.random.default_rng(1).integers(0, 512, 40),
                       jnp.int32)
    a = np.asarray(phi4_decoder.logits_fn(cfg)(params, toks))
    b = np.asarray(phi4_decoder.logits_fn(cfg, quant="fp8")(params, toks))
    assert np.max(np.abs(a - b)) > 1e-2
