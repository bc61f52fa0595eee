"""Speculative decoding: draft/verify over the multi-token paged append.

The acceptance invariant (CI-gated here and in BENCH_serve.json): for ANY
draft proposer — good, adversarial, or degenerate — the engine's committed
token streams are BIT-IDENTICAL to non-speculative greedy decode on every
decode-capable smoke arch. Acceptance only moves the speed dial: rate 0
degenerates to plain decode (one committed token per verify step), rate 1
commits K + 1 tokens per step. Logits are pinned allclose (the multi-token
program may fuse recurrent cells differently from the Q = 1 program —
low-order-bit wobble, same argmax; attention-only stacks stay bitwise).

Edges pinned: zero acceptance, full acceptance across the eviction
boundary (slot finishes mid-draft), eos truncation inside an accepted
run, and the ring-headroom fail-fasts.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, smoke_config
from repro.models.lm import attention as attn
from repro.models.lm import transformer as tf
from repro.serve import (EngineConfig, NgramProposer, Proposer, ServeEngine)
from repro.serve import backends as backends_lib

DECODE_ARCHS = [a for a in ARCH_IDS if smoke_config(a).supports_decode()]
KEY = jax.random.PRNGKey(0)
TOL = dict(rtol=1e-5, atol=1e-6)


@functools.lru_cache(maxsize=None)
def _setup(arch, impl="cadc"):
    cfg = smoke_config(arch, linear_impl=impl)
    params = tf.init(KEY, cfg)
    return cfg, params


def _staggered_workload(cfg, n=3, max_new=4):
    """Distinct prompts (oracle proposers key on them), staggered
    arrivals over 2 slots — queueing, eviction and slot reuse on the
    speculative path."""
    rng = np.random.RandomState(11)
    out = []
    for i in range(n):
        p = rng.randint(0, cfg.vocab_size, size=(3 + i,)).astype(np.int32)
        out.append((i, p, max_new))
    return out


def _run(cfg, params, workload, *, proposer=None, max_new=None, **kw):
    eng = ServeEngine(cfg, params, EngineConfig(
        n_slots=2, max_len=32, block_size=16, backend="paged",
        record_logits=True, **kw))
    if proposer is not None:
        eng.proposer = proposer
    eng.run([(a, p.copy(), g) for a, p, g in workload])
    return eng


def _assert_stream_parity(spec, base, *, logits_bitwise=False):
    assert sorted(spec.results) == sorted(base.results)
    for rid in base.results:
        rs, rb = spec.results[rid], base.results[rid]
        assert rs.tokens == rb.tokens, (
            f"req {rid}: speculative stream diverged from greedy")
        assert len(rs.logits) == len(rb.logits)
        for i, (ls, lb) in enumerate(zip(rs.logits, rb.logits)):
            if logits_bitwise:
                assert np.array_equal(ls, lb), (rid, i)
            else:
                np.testing.assert_allclose(ls, lb, **TOL,
                                           err_msg=f"req {rid} step {i}")


class OracleProposer(Proposer):
    """Cheating proposer for deterministic acceptance control: replays a
    baseline run's streams (acceptance 1 until the cap), optionally
    shifted by +1 mod vocab (guaranteed acceptance 0 — a proposal can
    never equal the greedy token it was derived from)."""

    def __init__(self, k, baseline, vocab, *, shift=0):
        super().__init__(k)
        self.vocab = vocab
        self.shift = shift
        self.streams = [
            np.concatenate([req.prompt,
                            np.asarray(req.tokens, np.int32)])
            for req in baseline.results.values()
        ]

    def propose(self, active, histories):
        out = np.zeros((len(histories), self.k), np.int32)
        for s, hist in enumerate(histories):
            if not active[s]:
                continue
            hist = np.asarray(hist)
            full = next(f for f in self.streams
                        if f.size >= hist.size
                        and np.array_equal(f[: hist.size], hist))
            cont = full[hist.size : hist.size + self.k]
            cont = np.concatenate(
                [cont, np.zeros(self.k - cont.size, np.int32)])
            out[s] = (cont + self.shift) % self.vocab
        return out


class TestSpeculativeParity:
    @pytest.mark.parametrize("arch", DECODE_ARCHS)
    def test_bit_identical_streams_all_archs(self, arch):
        """ngram-drafted speculative decode == plain greedy decode,
        token-for-token bitwise, through admission/eviction/slot reuse."""
        cfg, params = _setup(arch)
        wl = _staggered_workload(cfg)
        base = _run(cfg, params, wl)
        spec = _run(cfg, params, wl, spec_tokens=2)
        _assert_stream_parity(spec, base)
        sp = spec.telemetry.summary()["speculative"]
        assert 0.0 <= sp["accept_rate"] <= 1.0
        assert 1.0 <= sp["tokens_per_step"] <= 3.0

    @pytest.mark.parametrize("arch,dtype", [
        ("gemma3_1b", "float32"), ("recurrentgemma_9b", "float32"),
        ("gemma3_1b", "bfloat16")],
        ids=["gemma3_1b", "recurrentgemma_9b", "gemma3_1b-bf16"])
    def test_draft_model_proposer_parity(self, arch, dtype):
        """The shrunk-config draft model proposer: same invariant (its
        own dense caches track the committed frontier; rollouts are
        thrown away). It keeps its weights in the compute dtype, cast
        once when it is built."""
        cfg, params = _setup(arch)
        cfg = cfg.with_overrides(dtype=dtype)
        wl = _staggered_workload(cfg)
        base = _run(cfg, params, wl)
        spec = _run(cfg, params, wl, spec_tokens=3, spec_draft="model")
        _assert_stream_parity(spec, base)
        for a in jax.tree_util.tree_leaves(spec.proposer.params):
            if jnp.issubdtype(a.dtype, jnp.floating):
                assert a.dtype == jnp.dtype(dtype)

    def test_zero_acceptance_degenerates_to_decode(self):
        """All drafts rejected => every verify step commits exactly one
        token (the target's own greedy continuation) and the stream stays
        bitwise the plain decode stream."""
        cfg, params = _setup("gemma3_1b")
        wl = _staggered_workload(cfg)
        base = _run(cfg, params, wl)
        anti = OracleProposer(3, base, cfg.vocab_size, shift=1)
        spec = _run(cfg, params, wl, spec_tokens=3, proposer=anti)
        _assert_stream_parity(spec, base, logits_bitwise=True)
        sp = spec.telemetry.summary()["speculative"]
        assert sp["accept_rate"] == 0.0
        assert sp["tokens_per_step"] == 1.0

    def test_full_acceptance_eviction_boundary(self):
        """Oracle drafts (acceptance 1): slots commit K + 1 tokens per
        step and finish MID-DRAFT (max_new not a multiple of K + 1) —
        commits are capped at max_new, the slot is evicted with rejected
        draft state left behind, and its blocks drain back for reuse."""
        cfg, params = _setup("gemma3_1b")
        wl = _staggered_workload(cfg, max_new=5)  # 5 % (3+1) != 0
        base = _run(cfg, params, wl)
        oracle = OracleProposer(3, base, cfg.vocab_size)
        spec = _run(cfg, params, wl, spec_tokens=3, proposer=oracle)
        _assert_stream_parity(spec, base)
        for rid in spec.results:
            assert len(spec.results[rid].tokens) == 5
        sp = spec.telemetry.summary()["speculative"]
        assert sp["accept_rate"] > 0.5
        assert sp["tokens_per_step"] > 1.5
        stats = spec.tables.stats()
        assert all(s["free"] == s["pool_blocks"] for s in stats.values())
        assert any(s["total_allocs"] > s["pool_blocks"]
                   for s in stats.values())  # slot/block reuse happened

    def test_eos_truncates_inside_accepted_run(self):
        """An eos token landing inside an accepted draft run must cut the
        commit there (as sequential decode would have stopped) — parity
        includes the finish-by-eos schedule."""
        cfg, params = _setup("gemma3_1b")
        wl = _staggered_workload(cfg, max_new=6)
        probe = _run(cfg, params, wl)
        # pick the 3rd generated token of some request as eos: with full
        # acceptance the spec engine would otherwise commit past it
        eos = probe.results[0].tokens[2]
        base = _run(cfg, params, wl, eos_token=eos)
        oracle = OracleProposer(3, probe, cfg.vocab_size)
        spec = _run(cfg, params, wl, spec_tokens=3, proposer=oracle,
                    eos_token=eos)
        _assert_stream_parity(spec, base)
        assert spec.results[0].tokens[-1] == eos
        assert len(spec.results[0].tokens) <= len(probe.results[0].tokens)


class TestHeadroomAndFailFast:
    def test_local_ring_gets_window_plus_q_headroom(self):
        """The spec backend's local ring >= window + K (the no-wrap bound
        of attention_decode_paged), global ring >= max_len + K (no clip
        collisions when the last step drafts past the end), both at block
        granularity."""
        cfg, _ = _setup("gemma3_1b", impl="dense")
        be = backends_lib.PagedBackend(cfg, 2, 64, 16, spec_tokens=3)
        assert be.ring_len["local"] >= cfg.local_window + 3
        assert be.ring_len["global"] >= 64 + 3
        assert all(l % 16 == 0 for l in be.ring_len.values())
        base = backends_lib.PagedBackend(cfg, 2, 64, 16)
        assert base.ring_len["local"] == cfg.local_window

    def test_append_beyond_ring_fails_fast(self):
        """window < Q on a headroom-less ring: the multi-token append
        would scatter two draft tokens onto one ring entry — ValueError,
        not cache corruption."""
        cfg = smoke_config("gemma3_1b").with_overrides(local_window=8)
        p = attn.attn_init(jax.random.PRNGKey(0), cfg)
        pool = attn.init_paged_pool(cfg, 1, 8, np.float32)
        tbl = np.array([[0]], np.int32)
        x = np.zeros((1, 9, cfg.d_model), np.float32)  # Q=9 > ring 8
        with pytest.raises(ValueError, match="ring"):
            attn.attention_decode_paged(
                p, x, cfg, kind="local",
                position=np.array([0], np.int32), cache=pool,
                block_table=tbl)

    def test_dense_backend_rejects_spec(self):
        cfg, params = _setup("gemma3_1b", impl="dense")
        with pytest.raises(ValueError, match="paged"):
            ServeEngine(cfg, params, EngineConfig(
                n_slots=2, max_len=32, block_size=16, backend="dense",
                spec_tokens=2))

    def test_decode_prefill_rejects_spec(self):
        cfg, params = _setup("gemma3_1b", impl="dense")
        with pytest.raises(ValueError, match="batched"):
            ServeEngine(cfg, params, EngineConfig(
                n_slots=2, max_len=32, block_size=16,
                prefill_mode="decode", spec_tokens=2))

    def test_backend_without_spec_rejects_decode_spec(self):
        cfg, _ = _setup("gemma3_1b", impl="dense")
        be = backends_lib.PagedBackend(cfg, 2, 32, 16)
        with pytest.raises(ValueError, match="spec_tokens"):
            be.decode_spec(None, None, None, None, None)


class TestNgramProposer:
    def test_prompt_lookup_finds_repeated_pattern(self):
        prop = NgramProposer(3, max_ngram=3)
        hist = np.array([7, 1, 2, 3, 9, 1, 2, 3], np.int32)
        # trailing 3-gram [1,2,3] matched at index 1 -> continuation [9,1,2]
        out = prop.propose(np.array([True]), [hist])
        assert out.tolist() == [[9, 1, 2]]

    def test_longest_ngram_wins(self):
        prop = NgramProposer(2, max_ngram=3)
        # trailing [5,1]: 2-gram match at 0 -> [8, 5]; a 1-gram match of
        # [1] exists later (index 4 -> cont [9, 5]) but 2-gram is tried
        # first
        hist = np.array([5, 1, 8, 5, 1], np.int32)
        out = prop.propose(np.array([True]), [hist])
        assert out.tolist() == [[8, 5]]

    def test_fallback_repeats_last_token(self):
        prop = NgramProposer(4)
        hist = np.array([3, 1, 4, 2], np.int32)  # no repeats anywhere
        out = prop.propose(np.array([True]), [hist])
        assert out.tolist() == [[2, 2, 2, 2]]

    def test_short_continuation_padded(self):
        prop = NgramProposer(4, max_ngram=1)
        # match at 0, continuation [9, 1] -> padded with its last element
        hist = np.array([1, 9, 1], np.int32)
        out = prop.propose(np.array([True]), [hist])
        assert out.tolist() == [[9, 1, 1, 1]]

    def test_inactive_slots_untouched(self):
        prop = NgramProposer(2)
        out = prop.propose(np.array([False, True]),
                           [None, np.array([4, 4], np.int32)])
        assert out.shape == (2, 2) and out[0].tolist() == [0, 0]
