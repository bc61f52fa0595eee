"""Continuous-batching serve engine: paged-KV bit-parity + scheduling.

The acceptance invariant of the serve subsystem: the paged cache backend
(block tables over KV pools) produces BIT-IDENTICAL decode logits to the
dense per-slot ring caches on every decode-capable smoke arch — including
across finished-sequence eviction and slot/block reuse — and the
decode-mode engine reproduces the legacy fixed-batch serve_step loop
exactly.
"""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, smoke_config
from repro.launch import steps as steps_lib
from repro.models.lm import transformer as tf
from repro.serve import (BlockAllocator, EngineConfig, ServeEngine,
                         poisson_workload)

DECODE_ARCHS = [a for a in ARCH_IDS
                if smoke_config(a).supports_decode()]

KEY = jax.random.PRNGKey(0)


@functools.lru_cache(maxsize=None)
def _setup(arch, impl="cadc"):
    cfg = smoke_config(arch, linear_impl=impl)
    params = tf.init(KEY, cfg)
    return cfg, params


def _staggered_workload(cfg, n=3):
    """More requests than the 2 test slots, staggered arrivals, ragged
    prompts — forces queueing, eviction and slot reuse."""
    rng = np.random.RandomState(7)
    out = []
    for i in range(n):
        p = rng.randint(0, cfg.vocab_size, size=(3 + (i % 3),)).astype(np.int32)
        out.append((i, p, 3))
    return out


def _run(cfg, params, backend, workload, prefill_mode="batched", **kw):
    eng = ServeEngine(cfg, params, EngineConfig(
        n_slots=2, max_len=32, block_size=16, backend=backend,
        prefill_mode=prefill_mode, record_logits=True, **kw))
    eng.run([(a, p.copy(), g) for a, p, g in workload])
    return eng


class TestPagedParity:
    @pytest.mark.parametrize("arch", DECODE_ARCHS)
    def test_paged_bit_identical_to_dense(self, arch):
        """Same schedule, same params: every request's token stream AND
        per-token logits must agree bitwise between cache layouts,
        through slot eviction + reuse."""
        cfg, params = _setup(arch)
        wl = _staggered_workload(cfg)
        paged = _run(cfg, params, "paged", wl)
        dense = _run(cfg, params, "dense", wl)
        assert sorted(paged.results) == sorted(dense.results)
        for rid in paged.results:
            rp, rd = paged.results[rid], dense.results[rid]
            assert rp.tokens == rd.tokens, f"req {rid} tokens diverged"
            for i, (lp, ld) in enumerate(zip(rp.logits, rd.logits)):
                assert np.array_equal(lp, ld), (
                    f"req {rid} logits step {i}: max |d| = "
                    f"{np.abs(lp - ld).max()}")
        # the schedule really exercised reuse (3 requests over 2 slots)
        assert len(paged.results) > 2
        stats = paged.tables.stats()
        if stats:  # pure-recurrent stacks (xlstm) have no KV pools
            assert any(s["total_allocs"] > s["pool_blocks"]
                       for s in stats.values())
            assert all(s["free"] == s["pool_blocks"]
                       for s in stats.values())

    def test_decode_mode_prefill_parity(self):
        """The --prefill-via-decode path must hold the same paged/dense
        invariant (caches built through the decode step itself)."""
        cfg, params = _setup("gemma3_1b")
        wl = _staggered_workload(cfg)
        paged = _run(cfg, params, "paged", wl, prefill_mode="decode")
        dense = _run(cfg, params, "dense", wl, prefill_mode="decode")
        for rid in paged.results:
            assert paged.results[rid].tokens == dense.results[rid].tokens
            for lp, ld in zip(paged.results[rid].logits,
                              dense.results[rid].logits):
                assert np.array_equal(lp, ld)


class TestLegacyAnchor:
    @pytest.mark.parametrize("backend", ["dense", "paged"])
    def test_engine_matches_legacy_serve_loop(self, backend):
        """Uniform batch + decode-mode prefill == the old fixed-batch
        serve_step loop, token for token."""
        cfg, params = _setup("gemma3_1b")
        B, P, G, ML = 2, 4, 4, 32
        prompt = np.asarray(jax.random.randint(
            jax.random.PRNGKey(1), (B, P), 0, cfg.vocab_size, jnp.int32))

        caches = tf.init_caches(cfg, B, ML)
        serve_step = jax.jit(steps_lib.make_serve_step(cfg))
        tok = jnp.asarray(prompt[:, 0])
        for pos in range(P):
            nxt, _, caches = serve_step(params, tok,
                                        jnp.asarray(pos, jnp.int32), caches)
            tok = jnp.asarray(prompt[:, pos + 1]) if pos + 1 < P else nxt
        legacy = [np.asarray(tok)]
        for g in range(G - 1):
            tok, _, caches = serve_step(params, tok,
                                        jnp.asarray(P + g, jnp.int32), caches)
            legacy.append(np.asarray(tok))
        legacy = np.stack(legacy, 1)

        eng = ServeEngine(cfg, params, EngineConfig(
            n_slots=B, max_len=ML, block_size=16, backend=backend,
            prefill_mode="decode"))
        for b in range(B):
            eng.submit(prompt[b], G)
        eng.run()
        got = np.stack([np.asarray(eng.results[r].tokens)
                        for r in sorted(eng.results)])
        assert np.array_equal(got, legacy)

    def test_batched_prefill_consistent_with_decode_prefill(self):
        """Batched prefill builds caches in one forward; the first-token
        logits must match the token-at-a-time path to numerical noise
        (blockwise softmax vs incremental — not bitwise by design)."""
        cfg, params = _setup("gemma3_1b")
        wl = [(0, np.arange(1, 7, dtype=np.int32) % cfg.vocab_size, 3),
              (0, np.arange(2, 6, dtype=np.int32) % cfg.vocab_size, 3)]
        fast = _run(cfg, params, "paged", wl, prefill_mode="batched")
        slow = _run(cfg, params, "paged", wl, prefill_mode="decode")
        for rid in fast.results:
            lf, ls = fast.results[rid].logits[0], slow.results[rid].logits[0]
            np.testing.assert_allclose(lf, ls, rtol=2e-4, atol=2e-4)


    @pytest.mark.parametrize("arch", ["gemma3_1b", "lfm2_8b_a1b"])
    def test_engine_given_fp32_matches_given_cast(self, arch):
        """bf16 compute: an engine given float32 weights keeps them cast
        once, and serves the same tokens and logits, bit for bit, as an
        engine given the same weights already cast, and as programs that
        cast the float32 tree inside every call."""
        cfg, params = _setup(arch)
        cfg = cfg.with_overrides(dtype="bfloat16")
        wl = _staggered_workload(cfg)
        given_fp32 = _run(cfg, params, "paged", wl)
        given_cast = _run(cfg, steps_lib.cast_compute(params, cfg), "paged",
                          wl)
        in_step = ServeEngine(cfg, params, EngineConfig(
            n_slots=2, max_len=32, block_size=16, record_logits=True))
        in_step.params = params  # every program casts the fp32 tree
        in_step.run([(a, p.copy(), g) for a, p, g in wl])
        for other in (given_cast, in_step):
            assert sorted(other.results) == sorted(given_fp32.results)
            for rid, req in given_fp32.results.items():
                assert other.results[rid].tokens == req.tokens
                assert all(np.array_equal(a, b) for a, b in
                           zip(other.results[rid].logits, req.logits))


def _weight_converts(lowered_text: str, params) -> list:
    """Shapes of the float32 converts in a lowered program whose operand
    has the shape of a float32 weight leaf."""
    shapes = {"x".join(map(str, a.shape))
              for a in jax.tree_util.tree_leaves(params)
              if a.dtype == jnp.float32}
    found = re.findall(r"stablehlo\.convert [^\n]*\(tensor<([0-9x]*)xf32>\)",
                       lowered_text)
    return [s for s in found if s.rstrip("x") in shapes]


class TestKeptWeights:
    """The engine keeps its weights in the dtype its programs compute
    with, cast once when it is built."""

    @pytest.mark.parametrize("arch", ["gemma3_1b", "lfm2_8b_a1b"])
    def test_bf16_wire_keeps_bf16(self, arch):
        cfg, params = _setup(arch)
        cfg = cfg.with_overrides(dtype="bfloat16")
        eng = ServeEngine(cfg, params, EngineConfig(
            n_slots=2, max_len=32, block_size=16))
        for a in jax.tree_util.tree_leaves(eng.params):
            if jnp.issubdtype(a.dtype, jnp.floating):
                assert a.dtype == jnp.bfloat16
        fp32 = sum(a.nbytes for a in jax.tree_util.tree_leaves(params)
                   if a.dtype == jnp.float32)
        summary = eng.run(_staggered_workload(cfg))
        eng.reset_metrics()  # the counters outlive a reset
        for s in (summary, eng.telemetry.summary()):
            assert s["weights_cast_bytes"] == fp32
            assert s["weights_bytes"].get("float32", 0) == 0
            assert s["weights_bytes"]["bfloat16"] == fp32 // 2

    @pytest.mark.parametrize("case", ["bf16_wire_off", "float32_compute",
                                      "already_cast"])
    def test_nothing_to_cast_keeps_tree(self, case):
        """bf16_wire off, a float32 compute dtype, or a tree already in
        the compute dtype: the engine keeps the tree as given."""
        cfg, params = _setup("gemma3_1b")
        cfg = cfg.with_overrides(dtype="bfloat16")
        if case == "bf16_wire_off":
            cfg = cfg.with_overrides(bf16_wire=False)
        elif case == "float32_compute":
            cfg = cfg.with_overrides(dtype="float32")
        else:
            params = steps_lib.cast_compute(params, cfg)
        eng = ServeEngine(cfg, params, EngineConfig(
            n_slots=2, max_len=32, block_size=16))
        assert eng.params is params
        s = eng.telemetry.summary()
        assert s["weights_cast_bytes"] == 0
        assert sum(s["weights_bytes"].values()) == sum(
            a.nbytes for a in jax.tree_util.tree_leaves(params))

    @pytest.mark.parametrize("arch", ["gemma3_1b", "lfm2_8b_a1b"])
    def test_programs_convert_no_weight(self, arch):
        """Lowered with the kept tree, neither the decode nor the batched
        prefill program converts a float32 weight; lowered with the
        float32 tree, both do (the check sees a cast in the step)."""
        cfg, params = _setup(arch)
        cfg = cfg.with_overrides(dtype="bfloat16")
        eng = ServeEngine(cfg, params, EngineConfig(
            n_slots=2, max_len=32, block_size=16))
        n = eng.ecfg.n_slots
        tokens = jnp.zeros((n,), jnp.int32)
        batch = {"tokens": jnp.zeros((n, 8), jnp.int32)}
        lengths = jnp.full((n,), 8, jnp.int32)
        for tree, casts in ((eng.params, False), (params, True)):
            decode = eng.backend._decode.lower(
                tree, eng.caches, eng._device_tables(), tokens,
                tokens).as_text()
            prefill = eng._prefill_fn.lower(tree, batch, lengths).as_text()
            for text in (decode, prefill):
                assert bool(_weight_converts(text, params)) == casts


class TestScheduling:
    def test_slot_reuse_under_load(self):
        """8 Poisson requests over 2 slots: everything finishes, every
        request got exactly max_new tokens, blocks drain back to free."""
        cfg, params = _setup("gemma3_1b", impl="dense")
        wl = poisson_workload(n_requests=8, rate=1.5,
                              vocab_size=cfg.vocab_size,
                              prompt_len=(2, 6), max_new=(2, 4), seed=3)
        eng = ServeEngine(cfg, params, EngineConfig(
            n_slots=2, max_len=32, block_size=16, backend="paged"))
        summary = eng.run(wl)
        assert summary["requests_finished"] == 8
        for (_, _, g), rid in zip(wl, sorted(eng.results)):
            assert len(eng.results[rid].tokens) == g
        assert all(s["free"] == s["pool_blocks"]
                   for s in summary["blocks"].values())
        assert sum(summary["slot_uses"]) == 8  # every admission counted
        assert max(summary["slot_uses"]) > 1   # some slot really reused
        assert summary["tokens_per_s"] > 0
        assert summary["ttft_ms_p50"] > 0

    def test_admission_rejects_oversized(self):
        cfg, params = _setup("gemma3_1b", impl="dense")
        eng = ServeEngine(cfg, params, EngineConfig(
            n_slots=2, max_len=32, block_size=16))
        with pytest.raises(ValueError, match="max_len"):
            eng.submit(np.zeros(30, np.int32), 10)

    def test_rejects_unservable_pool(self):
        """n_blocks too small to map even one slot must fail fast, not
        head-of-line-block forever."""
        cfg, params = _setup("gemma3_1b", impl="dense")
        with pytest.raises(ValueError, match="admitted"):
            ServeEngine(cfg, params, EngineConfig(
                n_slots=2, max_len=32, block_size=16,
                n_blocks={"global": 1, "local": 1}))

    def test_vit_patches_change_output(self):
        """VLM serving: per-request image embeddings reach the prefill
        (the first frontend_len positions ARE the image, so distinct
        patches must yield distinct first-token logits). Prompts must
        span the image prefix — shorter ones are rejected, not silently
        truncated to a partial image."""
        cfg, params = _setup("internvl2_1b", impl="dense")
        prompt = np.arange(1, cfg.frontend_len + 3, dtype=np.int32)
        outs = []
        for fill in (0.0, 0.5):
            eng = ServeEngine(cfg, params, EngineConfig(
                n_slots=1, max_len=32, block_size=16,
                record_logits=True))
            patches = np.full((cfg.frontend_len, cfg.frontend_dim), fill,
                              np.float32)
            eng.submit(prompt, 2, patches=patches)
            eng.run()
            outs.append(eng.results[0].logits[0])
        assert not np.array_equal(outs[0], outs[1])
        eng = ServeEngine(cfg, params, EngineConfig(
            n_slots=1, max_len=32, block_size=16))
        with pytest.raises(ValueError, match="frontend_len"):
            eng.submit(np.arange(4, dtype=np.int32), 2,
                       patches=np.zeros((cfg.frontend_len,
                                         cfg.frontend_dim), np.float32))

    def test_block_allocator(self):
        a = BlockAllocator(4)
        got = a.alloc(3)
        assert sorted(got) == [0, 1, 2] and a.free_count == 1
        assert a.alloc(2) is None and a.free_count == 1
        a.free(got)
        assert a.free_count == 4 and a.high_water == 3

    def test_workload_deterministic(self):
        w1 = poisson_workload(n_requests=5, rate=0.5, vocab_size=100, seed=9)
        w2 = poisson_workload(n_requests=5, rate=0.5, vocab_size=100, seed=9)
        assert [(a, g) for a, _, g in w1] == [(a, g) for a, _, g in w2]
        assert all(np.array_equal(p1, p2)
                   for (_, p1, _), (_, p2, _) in zip(w1, w2))


class TestTelemetry:
    def test_psum_sparsity_tap(self):
        """CADC decode telemetry: per-layer gate-off fraction in [0, 1],
        one record per segmented linear on the decode path."""
        cfg, params = _setup("gemma3_1b")
        wl = _staggered_workload(cfg, n=2)
        eng = _run(cfg, params, "paged", wl, telemetry_every=1)
        summary = eng.telemetry.summary()
        sp = summary.get("psum_sparsity", {})
        assert sp, "no sparsity records tapped"
        for label, rec in sp.items():
            assert 0.0 <= rec["gate_off"] <= 1.0, (label, rec)
            assert 0.0 <= rec["exact_zero"] <= 1.0
            assert rec["segments"] >= 1
        # labels carry the layer position from the decode loop
        assert any(label.startswith("tail") for label in sp)

    def test_dense_impl_taps_nothing(self):
        cfg, params = _setup("gemma3_1b", impl="dense")
        wl = _staggered_workload(cfg, n=2)
        eng = _run(cfg, params, "paged", wl, telemetry_every=1)
        assert "psum_sparsity" not in eng.telemetry.summary()


class TestShardingSpecs:
    def test_paged_cache_specs_structure(self):
        from repro.launch.train import make_local_mesh
        from repro.parallel import sharding as shard_lib

        cfg, _ = _setup("gemma3_1b", impl="dense")
        caches = tf.init_paged_caches(
            cfg, n_slots=2, block_size=16,
            n_blocks={"global": 4, "local": 4}, max_len=32)
        mesh = make_local_mesh()
        specs = shard_lib.paged_cache_specs(
            jax.eval_shape(lambda: caches), cfg, mesh)
        flat_c = jax.tree_util.tree_leaves(caches)
        flat_s = jax.tree_util.tree_leaves(
            specs, is_leaf=lambda x: isinstance(
                x, jax.sharding.PartitionSpec))
        assert len(flat_c) == len(flat_s)
        named = shard_lib.to_named(specs, mesh)  # must all be realizable
        jax.device_put(caches, named)
