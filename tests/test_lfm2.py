"""LFM2-8B-A1B at smoke size: gated short-conv layers beside GQA with
QK-norm, leading dense layers, a sigmoid-routed expert layer that holds a
share of the experts, and the grouped CADC expert kernel.

The plain reference is the benchmark's (`bench/reference/
lfm2_moe_decoder.py`): the served path (ServeEngine, paged backend,
batched prefill, decode) is compared with its full forward on logits.
"""
import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke_config
from repro.kernels import cadc_gmm as gmm_lib
from repro.kernels import ops
from repro.core import cadc as cadc_lib
from repro.models.lm import attention as attn
from repro.models.lm import layers as ll
from repro.models.lm import moe as moe_lib
from repro.models.lm import shortconv
from repro.models.lm import transformer as tf
from repro.serve import EngineConfig, ServeEngine

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "bench"))
from reference import lfm2_moe_decoder as ref  # noqa: E402

KEY = jax.random.PRNGKey(0)


def smoke(**kw):
    return smoke_config("lfm2_8b_a1b", linear_impl="cadc", **kw)


def ref_cfg(cfg):
    """The reference's configuration keys for an ArchConfig."""
    m = cfg.moe
    return {
        "layer_types": ["full_attention" if k == "global" else k
                        for k in cfg.pattern_for_layers],
        "num_hidden_layers": cfg.n_layers,
        "num_dense_layers": cfg.n_dense_layers,
        "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads,
        "hidden_size": cfg.d_model, "crossbar_size": cfg.crossbar_size,
        "norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
        "vocab_size": cfg.vocab_size, "num_experts_per_tok": m.top_k,
        "routed_scaling_factor": 1,
        "first_expert": m.first_expert, "num_experts": m.held,
    }


def sharpened(params):
    """Routers x20 and a spread expert bias: at random init the top-k is
    a near tie that 1e-6 of rounding flips, and the comparison is of the
    cache and dispatch machinery, not of tie-breaking."""
    def walk(d):
        if isinstance(d, dict):
            return {k: (v * 20.0 if k == "router" else
                        v + 0.05 * jnp.arange(v.shape[-1]) / v.shape[-1]
                        if k == "expert_bias" else walk(v))
                    for k, v in d.items()}
        if isinstance(d, tuple):
            return tuple(walk(v) for v in d)
        return d
    return walk(params)


class TestConfig:
    def test_published_sizes(self):
        c = get_config("lfm2_8b_a1b")
        assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.head_dim,
                c.d_ff, c.vocab_size) == (24, 2048, 32, 8, 64, 7168, 65536)
        assert (c.moe.n_experts, c.moe.top_k, c.moe.d_expert) == (32, 4, 1792)
        kinds = c.pattern_for_layers
        assert kinds.count("conv") == 18 and kinds.count("global") == 6

    def test_layout_of_the_cut(self):
        """8 layers: conv conv | attn conv conv conv | attn conv."""
        c = get_config("lfm2_8b_a1b", n_layers=8)
        assert tf.lead_kinds(c) == ("conv", "conv")
        # one whole repeat of the period: inline, not a scan of one
        assert tf.layout(c) == (0, ("global", "conv", "conv", "conv"),
                                ("global", "conv", "conv", "conv", "global",
                                 "conv"))
        full = get_config("lfm2_8b_a1b")
        assert tf.layout(full)[0] == 4 and len(tf.layout(full)[2]) == 6


class TestGroupedKernel:
    @pytest.mark.parametrize("sizes", [
        (5, 0, 17, 3),        # an empty expert, counts not a tile multiple
        (0, 0, 40, 0),        # every row to one expert
        (16, 16, 1, 0),       # whole tiles, a one-row group, empty last
        (0, 0, 0, 0),         # nothing routed here
    ])
    def test_interpret_matches_oracle(self, sizes):
        e, d, n, xbar, bm = 4, 128, 96, 64, 16
        gs = jnp.array(sizes, jnp.int32)
        n_tiles = gmm_lib.tile_bound(max(sum(sizes), 1), e, bm)
        start, _, _ = gmm_lib.tile_layout(gs, bm, n_tiles)
        x = jnp.zeros((n_tiles * bm, d))
        rows = []
        for i, cnt in enumerate(sizes):
            v = jax.random.normal(jax.random.fold_in(KEY, i), (cnt, d))
            x = x.at[int(start[i]): int(start[i]) + cnt].set(v)
            rows.append(v)
        w = jax.random.normal(KEY, (e, d // xbar, xbar, n)) / 10
        got = ops.cadc_gmm(x, w, gs, crossbar_size=xbar, block_m=bm,
                           impl="interpret")
        oracle = ops.cadc_gmm(x, w, gs, crossbar_size=xbar, block_m=bm,
                              impl="xla")
        np.testing.assert_allclose(np.asarray(got), np.asarray(oracle),
                                   rtol=1e-5, atol=1e-5)
        for i, cnt in enumerate(sizes):
            want = cadc_lib.cadc_matmul(rows[i], w[i].reshape(d, n),
                                        crossbar_size=xbar, fn="relu")
            np.testing.assert_allclose(
                np.asarray(got[int(start[i]): int(start[i]) + cnt]),
                np.asarray(want), rtol=1e-5, atol=1e-5)
        live = int(sum(-(-c // bm) for c in sizes)) * bm
        assert not np.any(np.asarray(got[live:]))

    def test_moe_layer_kernel_matches_oracle(self):
        cfg = smoke(kernel_impl="xla")
        p = moe_lib.moe_init(KEY, cfg)
        x = jax.random.normal(KEY, (2, 12, cfg.d_model))
        y_x, _, r_x = moe_lib.moe_apply(p, x, cfg)
        y_i, _, r_i = moe_lib.moe_apply(
            p, x, cfg.with_overrides(kernel_impl="interpret"))
        assert np.array_equal(np.asarray(r_x["rows"]), np.asarray(r_i["rows"]))
        assert r_x["top"].shape == (24, 4)
        np.testing.assert_allclose(np.asarray(y_i), np.asarray(y_x),
                                   rtol=1e-5, atol=1e-5)


class TestExpertShare:
    def test_four_shares_sum_to_the_uncut_layer(self):
        """Each share routes over all 16 experts and adds its own 4; the
        four shares' outputs add up to the reference layer that holds
        all 16."""
        full = smoke().with_overrides(moe=dataclasses.replace(
            smoke().moe, n_held=0))
        p = moe_lib.moe_init(KEY, full)
        p["expert_bias"] = 0.1 * jax.random.normal(KEY, (16,))
        x = jax.random.normal(jax.random.fold_in(KEY, 1), (1, 20, 128))
        total = 0.0
        for first in range(0, 16, 4):
            share = full.with_overrides(moe=dataclasses.replace(
                full.moe, first_expert=first, n_held=4))
            sp = dict(p, **{k: p[k][first: first + 4]
                            for k in ("w_gate", "w_up", "w_down")})
            y, _, routed = moe_lib.moe_apply(sp, x, share)
            total = total + y.astype(jnp.float32)
            assert routed["rows"].shape == (4,)
        uncut, _, routed = moe_lib.moe_apply(p, x, full)
        assert int(routed["rows"].sum()) == 20 * 4
        y_ref = ref._moe(x[0], p, ref_cfg(full), None)[0]
        np.testing.assert_allclose(np.asarray(total[0]), np.asarray(y_ref),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(uncut[0]), np.asarray(y_ref),
                                   rtol=1e-4, atol=1e-4)

    def test_padding_rows_reach_no_expert(self):
        cfg = smoke()
        p = moe_lib.moe_init(KEY, cfg)
        x = jax.random.normal(KEY, (2, 8, 128))
        valid = jnp.arange(8)[None, :] < jnp.array([[8], [3]])
        y, _, routed = moe_lib.moe_apply(p, x, cfg, valid)
        y_all, _, routed_all = moe_lib.moe_apply(p, x, cfg)
        assert int(routed["rows"].sum()) <= int(routed_all["rows"].sum())
        assert not np.any(np.asarray(y[1, 3:]))
        np.testing.assert_allclose(np.asarray(y[0]), np.asarray(y_all[0]),
                                   rtol=1e-6, atol=1e-6)


class TestShortConv:
    @pytest.mark.parametrize("lengths", [(9, 1), (2, 5), (0, 12)])
    def test_parallel_prefill_state_equals_decode(self, lengths):
        """The wave's state at each slot's own length (zeros below two
        rows) equals what token-by-token decode leaves behind."""
        cfg = smoke()
        p = shortconv.shortconv_init(KEY, cfg)
        s = 12
        x = jax.random.normal(KEY, (2, s, cfg.d_model))
        lens = jnp.array(lengths)
        y, state = shortconv.shortconv_prefill(p, x, cfg, lens)
        st = run = shortconv.shortconv_init_state(cfg, 2)
        ys = []
        for t in range(s):
            yt, run = shortconv.shortconv_decode(p, x[:, t:t + 1], cfg, run)
            keep = (t < lens)[:, None, None]
            st = shortconv.ConvState(jnp.where(keep, run.conv, st.conv))
            ys.append(yt)
        np.testing.assert_allclose(np.asarray(state.conv),
                                   np.asarray(st.conv), rtol=1e-6,
                                   atol=1e-6)
        np.testing.assert_allclose(np.asarray(y),
                                   np.asarray(jnp.concatenate(ys, 1)),
                                   rtol=1e-5, atol=1e-5)


class TestQKNorm:
    def test_off_leaves_phi4_attention_as_it_was(self):
        """QK-norm off (phi4) adds no weights and q, k are the rotated
        projections exactly as before the option existed."""
        cfg = smoke_config("phi4_mini_38b", linear_impl="cadc")
        p = attn.attn_init(KEY, cfg)
        assert set(p) == {"wq", "wk", "wv", "wo"}
        x = jax.random.normal(KEY, (2, 8, cfg.d_model))
        pos = jnp.arange(8)[None, :]
        q, k, v = attn._qkv(p, x, cfg, pos)
        h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        q0 = ll.rope(ll.linear_apply(p["wq"], x, cfg).reshape(2, 8, h, hd),
                     pos, cfg.rope_theta)
        k0 = ll.rope(ll.linear_apply(p["wk"], x, cfg).reshape(2, 8, kv, hd),
                     pos, cfg.rope_theta)
        assert np.array_equal(np.asarray(q), np.asarray(q0))
        assert np.array_equal(np.asarray(k), np.asarray(k0))

    def test_on_normalises_each_head(self):
        cfg = smoke()
        p = attn.attn_init(KEY, cfg)
        x = jax.random.normal(KEY, (1, 4, cfg.d_model)) * 7.0
        q, k, _ = attn._qkv(p, x, cfg, jnp.zeros((1, 4), jnp.int32))
        rms = np.sqrt(np.mean(np.square(np.asarray(q)), axis=-1))
        np.testing.assert_allclose(rms, 1.0, rtol=1e-3)


class TestReference:
    def test_walks_the_configured_kinds(self):
        """The reference lays the layers out from the configuration and
        refuses weights of another kind in their place."""
        cfg = smoke(kernel_impl="xla")
        params = tf.init(KEY, cfg)
        rc = ref_cfg(cfg)
        toks = jnp.arange(6)
        none = -jnp.ones((6, 6, 4), jnp.int32)
        ref.forward(params, toks, rc, None, none)
        for i, swap in ((2, "conv"), (3, "full_attention")):
            kinds = list(rc["layer_types"])
            kinds[i] = swap
            with pytest.raises(ValueError, match=f"layer {i} "):
                ref.forward(params, toks, dict(rc, layer_types=kinds))
        with pytest.raises(ValueError, match="layer 2 "):
            ref.forward(params, toks, dict(rc, num_dense_layers=3))

    def test_forced_selection_keeps_its_own_choice(self):
        """Given the router's own choice, nothing moves; given another,
        the logits move and the first MoE layer still reports its own."""
        cfg = smoke(kernel_impl="xla")
        params = tf.init(KEY, cfg)
        rc = ref_cfg(cfg)
        toks = jax.random.randint(KEY, (10,), 0, cfg.vocab_size)
        none = -jnp.ones((6, 10, 4), jnp.int32)
        logits, own, _, inputs, final = ref.forward(params, toks, rc, None,
                                                    none)
        assert inputs.shape == (6, 10, cfg.d_model)
        assert final.shape == (10, cfg.d_model)
        same = ref.forward(params, toks, rc, None, own)
        np.testing.assert_array_equal(np.asarray(same[0]),
                                      np.asarray(logits))
        other = (own + 1) % cfg.moe.n_experts
        moved, own2 = ref.forward(params, toks, rc, None, other)[:2]
        assert not np.allclose(np.asarray(moved), np.asarray(logits))
        np.testing.assert_array_equal(np.asarray(own2[0]),
                                      np.asarray(own[0]))


class TestServedAgainstReference:
    @pytest.mark.parametrize("backend", ["paged", "dense"])
    def test_engine_logits_match_reference(self, backend):
        """Prefill and then decoding through the caches, batched over
        slots with ragged prompts, agree with the reference's full
        forward at every served position."""
        cfg = smoke(kernel_impl="xla")
        params = sharpened(tf.init(KEY, cfg))
        eng = ServeEngine(cfg, params, EngineConfig(
            n_slots=2, max_len=48, block_size=16, backend=backend,
            record_logits=True))
        rng = np.random.RandomState(3)
        for n in (11, 1, 6):
            eng.submit(rng.randint(0, cfg.vocab_size, n), 9)
        eng.run()
        fwd = ref.logits_fn(ref_cfg(cfg))
        rows = [r for _, r in eng.telemetry.routed]
        assert rows and all(r.shape == (6, 4) for r in rows)
        for req in eng.results.values():
            seq = np.concatenate([req.prompt, req.tokens[:-1]])
            logits, own = fwd(params, jnp.asarray(seq),
                              -jnp.ones((6, seq.size, 4), jnp.int32))[:2]
            got = np.stack(req.logits)
            want = np.asarray(logits)[req.prompt.size - 1:]
            np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)
            assert req.tokens == list(np.argmax(want, -1))
            # the program's own selection of every token it computed
            routes = np.sort(np.concatenate(req.routes, axis=1), axis=-1)
            assert np.array_equal(routes, np.asarray(own))

    def test_decode_matches_train_forward(self):
        cfg = smoke(kernel_impl="xla")
        params = sharpened(tf.init(KEY, cfg))
        toks = jax.random.randint(KEY, (2, 20), 0, cfg.vocab_size)
        train, _ = tf.forward_train(params, {"tokens": toks}, cfg)
        caches = tf.init_caches(cfg, 2, 20)
        step = jax.jit(lambda p, t, pos, c: tf.decode_step(p, t, pos, c, cfg))
        dec = []
        for t in range(20):
            logits, caches = step(params, toks[:, t], jnp.int32(t), caches)
            dec.append(logits)
        np.testing.assert_allclose(np.asarray(jnp.stack(dec, 1)),
                                   np.asarray(train), rtol=2e-3, atol=2e-3)

    def test_speculative_streams_equal_greedy(self):
        """Draft/verify over the conv states' per-token rollback commits
        what plain greedy decode does."""
        cfg = smoke(kernel_impl="xla")
        params = tf.init(KEY, cfg)
        rng = np.random.RandomState(5)
        prompts = [rng.randint(0, cfg.vocab_size, n) for n in (7, 12)]
        out = []
        for k in (0, 3):
            eng = ServeEngine(cfg, params, EngineConfig(
                n_slots=2, max_len=48, block_size=16, spec_tokens=k))
            for pr in prompts:
                eng.submit(pr, 10)
            eng.run()
            out.append([eng.results[i].tokens for i in sorted(eng.results)])
        assert out[0] == out[1]
