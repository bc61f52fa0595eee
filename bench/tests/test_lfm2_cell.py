"""The lfm2 cell at a size a CPU test can hold: its configuration through
the harness, the comparison that decides `correct`, its control, and the
readers of its four per-layer metrics on a synthetic record."""
import json
import time

import jax.numpy as jnp
import numpy as np
import pytest

import control
import run
import smoke
from conftest import BENCH

ROOT = BENCH.parent
CELL = smoke.cell("lfm2-8b-a1b-cadc", "chat-backlog")


def lfm2(kernel="xla"):
    """The cell's configuration at smoke widths: 8 layers, d 128, 16
    experts of which this chip holds 4, crossbar 64."""
    cfg = smoke.load("configs", "lfm2-8b-a1b-cadc")
    cfg.update(hidden_size=128, num_attention_heads=4, num_key_value_heads=2,
               intermediate_size=192, moe_intermediate_size=128,
               vocab_size=512, crossbar_size=64, num_experts=4)
    cfg["published"] = dict(cfg["published"], num_experts=16)
    cfg["program"]["overrides"].update(
        d_model=128, n_heads=4, n_kv_heads=2, head_dim=32, d_ff=192,
        vocab_size=512, crossbar_size=64, kernel_impl=kernel, attn_chunk=64)
    cfg["program"]["moe"] = dict(cfg["program"]["moe"], n_held=4,
                                 n_experts=16, d_expert=128)
    return cfg


def run_small(cfg, seed, ctl=False, seconds=2.0):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    return run.run_loaded(bench, CELL, cfg, smoke.serve_mix("chat-backlog"),
                          seed, seconds, False, t_start=time.perf_counter(),
                          require_chip=False, control=ctl)


# At smoke widths both readings are far smaller than at the cell's (the
# program reads 0 on this seed); a limit made as the cell's is, above
# twice the bf16-rounded reference's logit error, lies between them.
SMOKE_GAP_LIMIT = 0.008
# Routing flips at smoke widths: the program 0.07-0.08, the bf16-rounded
# reference 0.05, a program that drops the expert bias 0.49-0.54, the fp8
# control 0.57-0.59 (the cell's size reads far more, and its limit is 0.7)
SMOKE_FLIP_LIMIT = 0.2


def test_cell_is_correct_and_control_is_not():
    cfg = lfm2()
    cfg["limits"] = dict(cfg["limits"], token_logit_gap=SMOKE_GAP_LIMIT,
                         route_flip_share=SMOKE_FLIP_LIMIT)
    out = run_small(cfg, 2**31 + 61, ctl=True)
    assert out["correct"] is True, out["checks"]
    ctl = out["record"]["control"]
    assert ctl["token_logit_gap"] > 3 * out["checks"]["token_logit_gap"][
        "value"]
    # the control follows its own routing and flips far more choices
    # than the program or the bf16-rounded reference
    assert ctl["route_pairs"] > 0
    flips = out["checks"]["route_flip_share"]
    assert flips["value"] < flips["limit"] < ctl["route_flip_share"]
    assert 0 < ctl["bf16_flip_share"] < flips["limit"]
    assert control.control_correct(out) is False
    assert out["metrics"]["output_tokens_per_s"]["value"] > 0
    steps = out["record"]["steps"]
    assert any(kind == "decode" for s in steps for kind, _ in s["routed"])


def test_served_token_altered_is_not_correct(monkeypatch):
    from repro.serve import backends

    decode = backends.PagedBackend.decode

    def altered(self, *a, **k):
        # every slot's token: the checked sample is 16 of 16 slots' requests
        nxt, logits, caches = decode(self, *a, **k)
        return jnp.argmin(logits, axis=-1).astype(nxt.dtype), logits, caches

    monkeypatch.setattr(backends.PagedBackend, "decode", altered)
    out = run_small(lfm2(), 2**31 + 62)
    assert out["correct"] is False


def test_routing_altered_is_not_correct(monkeypatch):
    """A program that selects its experts without the expert bias serves
    tokens close to the reference's, but the reference, following that
    selection, counts its own choice elsewhere too often."""
    from repro.models.lm import moe

    route = moe._route

    def unbiased(p, tokens, cfg):
        return route(dict(p, expert_bias=0.0 * p["expert_bias"]), tokens,
                     cfg)

    monkeypatch.setattr(moe, "_route", unbiased)
    cfg = lfm2()
    cfg["limits"] = dict(cfg["limits"], route_flip_share=SMOKE_FLIP_LIMIT)
    out = run_small(cfg, 2**31 + 63)
    flips = out["checks"]["route_flip_share"]
    assert flips["value"] > flips["limit"]
    assert out["correct"] is False


def test_whitened_routers_spread_the_load():
    """Random routers read the CADC linears' common offset and send every
    token to the same experts (one takes a quarter of the choices, the
    most an expert can with top-4); whitened, every layer spreads fresh
    tokens over all 16 experts."""
    import jax.numpy as jnp

    from drivers import serve_lfm2 as drv
    from reference import lfm2_moe_decoder as ref

    cfg = lfm2()
    arch = drv.program_config(cfg)
    fwd = ref.logits_fn(dict(cfg, first_expert=0))
    none = -jnp.ones((6, 64, 4), jnp.int32)

    def tokens(seed):
        return jnp.asarray(np.random.default_rng(seed).integers(0, 512, 64),
                           jnp.int32)

    def shares(params):
        own = np.asarray(fwd(params, tokens(2), none)[1])
        return [np.bincount(o.ravel(), minlength=16) / o.size for o in own]

    params = drv.make_weights(arch, 1)
    assert max(s.max() for s in shares(params)) == 0.25
    white = drv.whiten_routers(params, fwd, tokens(1), 6, 4)
    for s in shares(white):
        assert s.min() > 0 and s.max() < 0.2


def test_whitened_head_decodes_distinct_tokens():
    """The tied table reads the final stream's common offset as the same
    few tokens at every position (9 of 64 positions' greedy tokens differ
    here); with the table orthogonal to the stream's mean direction, the
    greedy token follows the position (56)."""
    from drivers import serve_lfm2 as drv
    from reference import lfm2_moe_decoder as ref

    cfg = lfm2()
    arch = drv.program_config(cfg)
    fwd = ref.logits_fn(dict(cfg, first_expert=0))
    none = -jnp.ones((6, 64, 4), jnp.int32)

    def tokens(seed):
        return jnp.asarray(np.random.default_rng(seed).integers(0, 512, 64),
                           jnp.int32)

    def distinct(params):
        logits = np.asarray(fwd(params, tokens(2), none)[0])
        return len(set(logits.argmax(axis=1).tolist()))

    params = drv.whiten_routers(drv.make_weights(arch, 1), fwd, tokens(1),
                                6, 4)
    assert distinct(params) <= 16
    assert distinct(drv.whiten_head(params, fwd, tokens(1), 6, 4)) >= 40
