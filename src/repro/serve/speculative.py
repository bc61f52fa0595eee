"""Draft proposers for engine-side speculative decoding.

The engine's draft/verify loop (ServeEngine._spec_decode_step) is
proposer-agnostic: each decode iteration a proposer offers K draft tokens
per active slot, the TARGET model scores all K+1 positions in ONE
multi-token `decode_step_spec` call, and the longest draft prefix matching
the target's own greedy continuations is committed (plus the bonus token
that falls out of the last scored position). Correctness never depends on
the proposer — a rejected draft costs one wasted verify lane, an accepted
one saves a whole decode step — so the committed stream is bit-identical
to non-speculative greedy decode for ANY proposer (the CI-gated invariant
of tests/test_speculative.py and BENCH_serve.json's speculative section).

Two proposers, selected by EngineConfig.spec_draft:

  * `NgramProposer` ("ngram") — prompt-lookup decoding: match the longest
    recent n-gram of the slot's token history (prompt + committed tokens)
    against its earlier occurrences and propose the tokens that followed
    the most recent match. Stateless per step, zero model cost; strong on
    the repetitive continuations greedy decode tends to fall into.
  * `DraftModelProposer` ("model") — a shrunk-config draft model (fewer
    layers, same vocab/tokenizer-free synthetic workload) runs K cheap
    sequential decode steps per engine iteration. It keeps its own dense
    per-slot caches mirroring the engine's committed frontier: proposals
    roll out on a THROWAWAY cache copy (jax pytrees are immutable — the
    pre-rollout reference IS the snapshot), and `on_commit` re-feeds the
    tokens the target actually committed, so draft state never contains
    speculation the target rejected.
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.launch import steps as steps_lib
from repro.models.lm import transformer as tf
from repro.serve import backends as backends_lib


class Proposer:
    """Interface the engine drives. `k` drafts per slot per step."""

    def __init__(self, k: int):
        if k < 1:
            raise ValueError(f"need at least one draft token, got k={k}")
        self.k = k

    def on_admit(self, admitted: Sequence[Tuple[int, object]]) -> None:
        """Called after the target's prefill for newly admitted slots:
        `admitted` is [(slot, Request)] with Request.tokens[0] (the
        target's first token) already present."""

    def propose(self, active: np.ndarray,
                histories: List[Optional[np.ndarray]]) -> np.ndarray:
        """[n_slots, k] int32 draft tokens. `histories[s]` is the full
        committed token stream (prompt + generated) of active slot s."""
        raise NotImplementedError

    def on_commit(self, committed: List[Optional[np.ndarray]]) -> None:
        """Called once per step with the tokens actually committed per
        slot (None/empty for inactive slots) — the only channel through
        which stateful proposers may advance their frontier."""


class NgramProposer(Proposer):
    """Prompt-lookup decoding (arXiv:2304.04487-style, model-free).

    For n from `max_ngram` down to 1: take the history's trailing n-gram,
    find its most recent earlier occurrence, and propose the k tokens
    that followed it (padded by repeating the final proposal when the
    match sits near the end). Falls back to repeating the last token —
    a deterministic degenerate draft that keeps the verify math exercised
    even at acceptance rate 0."""

    def __init__(self, k: int, max_ngram: int = 3):
        super().__init__(k)
        if max_ngram < 1:
            raise ValueError("max_ngram must be >= 1")
        self.max_ngram = max_ngram

    def _propose_one(self, hist: np.ndarray) -> np.ndarray:
        k = self.k
        last = int(hist[-1])
        for n in range(min(self.max_ngram, hist.size - 1), 0, -1):
            tail = hist[-n:]
            win = np.lib.stride_tricks.sliding_window_view(hist, n)
            starts = np.flatnonzero((win == tail).all(axis=1))
            starts = starts[starts < hist.size - n]  # earlier occurrences
            if starts.size == 0:
                continue
            i = int(starts[-1])  # most recent match
            cont = hist[i + n : i + n + k]
            if cont.size == 0:
                continue
            pad = int(cont[-1])
            return np.concatenate(
                [cont, np.full(k - cont.size, pad, hist.dtype)])
        return np.full(k, last, np.int32)

    def propose(self, active, histories):
        out = np.zeros((len(histories), self.k), np.int32)
        for s, hist in enumerate(histories):
            if active[s]:
                out[s] = self._propose_one(np.asarray(hist, np.int32))
        return out


def default_draft_config(cfg: ArchConfig) -> ArchConfig:
    """Shrink the target config to a cheap draft: one pattern-unit's worth
    of layers (at least 1), everything else — vocab, d_model, frontend,
    CADC knobs — unchanged so the draft serves the same workload."""
    return cfg.with_overrides(
        n_layers=max(1, len(cfg.pattern) // 2),
        name=cfg.name + "-draft")


class DraftModelProposer(Proposer):
    """K sequential greedy steps of a shrunk draft model per engine step.

    State = dense per-slot caches + (pos, last) vectors mirroring the
    engine's COMMITTED frontier exactly: `propose` rolls the draft forward
    on a throwaway cache reference (never stored), `on_commit` advances
    the real caches by re-feeding the committed tokens under a per-slot
    active mask (slots that committed fewer tokens — or none — keep their
    old state bit-for-bit)."""

    def __init__(self, k: int, cfg: ArchConfig, n_slots: int, max_len: int,
                 *, draft_cfg: Optional[ArchConfig] = None, seed: int = 1):
        super().__init__(k)
        self.cfg_d = draft_cfg or default_draft_config(cfg)
        if self.cfg_d.vocab_size != cfg.vocab_size:
            raise ValueError(
                f"draft vocab {self.cfg_d.vocab_size} != target vocab "
                f"{cfg.vocab_size}: proposals would not be target tokens")
        self.n_slots = n_slots
        # kept in the compute dtype, as the engine keeps the target's
        self.params, _ = backends_lib.compute_weights(
            tf.init(jax.random.PRNGKey(seed), self.cfg_d), self.cfg_d)
        # + k: the draft rolls out past the committed frontier, and its
        # global rings must hold those positions without clip collisions
        self.backend = backends_lib.DenseBackend(self.cfg_d, n_slots,
                                                 max_len + k)
        self.caches = self.backend.init_caches()
        self.pos = np.zeros(n_slots, np.int32)
        self.last = np.zeros(n_slots, np.int32)
        self._prefill = jax.jit(
            steps_lib.make_batched_prefill_step(self.cfg_d))
        self._rollout = jax.jit(self._rollout_impl)
        self._advance = jax.jit(self._advance_impl, donate_argnums=(1,))

    # -- jitted programs ------------------------------------------------

    def _rollout_impl(self, params, caches, tokens, pos):
        params = steps_lib.cast_compute(params, self.cfg_d)
        drafts = []
        for _ in range(self.k):  # static K, unrolled
            logits, caches = tf.decode_step(params, tokens, pos, caches,
                                            self.cfg_d)
            tokens = jnp.argmax(logits, -1).astype(jnp.int32)
            drafts.append(tokens)
            pos = pos + 1
        return jnp.stack(drafts, axis=1)  # [n_slots, k]; caches discarded

    def _advance_impl(self, params, caches, tokens, pos, active):
        _, new = tf.decode_step(steps_lib.cast_compute(params, self.cfg_d),
                                tokens, pos, caches, self.cfg_d)

        def one(kind, stacked, old_c, new_c):
            axis = 1 if stacked else 0

            def mix(o, nl):
                m = backends_lib._mask_rows(active, nl, axis)
                return jnp.where(m, nl, o)

            return jax.tree_util.tree_map(mix, old_c, new_c)

        return backends_lib.map_layer_caches(caches, new, self.cfg_d, one)

    # -- engine hooks ---------------------------------------------------

    def on_admit(self, admitted) -> None:
        if not admitted:
            return
        from repro.serve.engine import make_prefill_batch
        n = self.n_slots
        mask = np.zeros(n, bool)
        for slot, _ in admitted:
            mask[slot] = True
        self.caches = self.backend.reset_slots(self.caches,
                                               jnp.asarray(mask))
        # the ONE prefill-batch builder (shared with the engine): the
        # draft frontier mirrors the target's only while the layouts match
        batch, lengths, slot_ids = make_prefill_batch(self.cfg_d, n,
                                                      admitted)
        _, _, contribs, _ = self._prefill(self.params, batch, lengths)
        self.caches = self.backend.write_prefill(
            self.caches, contribs, slot_ids, lengths, None)
        for slot, req in admitted:
            # the frontier tracks the TARGET's commits: its first token,
            # not the draft model's own prediction
            self.pos[slot] = req.prompt.size
            self.last[slot] = req.tokens[0]

    def propose(self, active, histories):
        del histories  # the draft caches ARE the history
        drafts = self._rollout(self.params, self.caches,
                               jnp.asarray(self.last),
                               jnp.asarray(self.pos))
        return np.asarray(drafts)

    def on_commit(self, committed) -> None:
        n = self.n_slots
        counts = np.array([0 if c is None else len(c) for c in committed])
        cmax = int(counts.max()) if counts.size else 0
        if cmax == 0:
            return
        # inputs to process = [previous last, committed[:-1]]; the new
        # last committed token becomes next step's first input
        feed = np.zeros((cmax, n), np.int32)
        act = np.zeros((cmax, n), bool)
        for s, c in enumerate(committed):
            if counts[s] == 0:
                continue
            inputs = np.concatenate([[self.last[s]],
                                     np.asarray(c[:-1], np.int32)])
            feed[: inputs.size, s] = inputs
            act[: inputs.size, s] = True
        for t in range(cmax):
            self.caches = self._advance(
                self.params, self.caches, jnp.asarray(feed[t]),
                jnp.asarray(self.pos + t), jnp.asarray(act[t]))
        for s, c in enumerate(committed):
            if counts[s]:
                self.pos[s] += counts[s]
                self.last[s] = int(np.asarray(c)[-1])


def make_proposer(name: str, k: int, cfg: ArchConfig, n_slots: int,
                  max_len: int, **kw) -> Proposer:
    if name == "ngram":
        return NgramProposer(k, **kw)
    if name == "model":
        return DraftModelProposer(k, cfg, n_slots, max_len, **kw)
    raise ValueError(f"unknown draft proposer {name!r} "
                     "(expected 'ngram' or 'model')")
