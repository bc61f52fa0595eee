"""ServeEngine: continuous batching over the CADC decode path.

One engine iteration = (admit waiting requests into free slots) ->
(batched prefill for the admissions) -> (one decode step across all
slots). Every slot runs at its own sequence position (the per-slot
position vectors PR 3 added to the decode path); finished sequences are
evicted, their slot and — under the paged backend — their physical KV
blocks immediately reusable. Admission is FIFO with head-of-line
blocking on slot/block availability (priority scheduling is a ROADMAP
item).

Prefill modes:
  * 'batched' (default): one full-sequence forward for all admissions of
    the iteration (ragged prompt lengths; transformer.forward_prefill),
    cache contributions scatter-inserted in the cache layout's native
    format. First token falls out of the prefill logits — TTFT is one
    forward, not P decode steps.
  * 'decode': the legacy token-at-a-time path — each prefill-phase slot
    feeds its next prompt token through the ordinary decode step. Slower,
    but preserves the cache-consistency invariant exactly (decode-built
    caches), which the parity tests anchor on.

Speculative decoding (EngineConfig.spec_tokens = K > 0): decode
iterations become draft/verify steps — a proposer (serve.speculative)
offers K tokens per slot, the target scores all K+1 positions in ONE
multi-token decode_step_spec call, and greedy verification commits the
longest draft prefix matching the target's own continuations plus the
bonus token. Slots advance by their own acceptance count (variable-
advance position vectors, 1..K+1 per step). THE invariant, gated in
BENCH_serve.json and tests/test_speculative.py: committed token streams
are bit-identical to spec_tokens=0 greedy decode for ANY proposer —
acceptance only moves throughput. Requires the paged backend (rings get
window+K / max_len+K draft headroom) and batched prefill. docs/serving.md
documents the lifecycle, the ring-wrap semantics and the telemetry.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.launch import steps as steps_lib
from repro.launch.steps import cast_compute
from repro.models.lm import layers as ll
from repro.models.lm import transformer as tf
from repro.serve import backends as backends_lib
from repro.serve.blocks import BlockTables
from repro.serve.telemetry import Telemetry

IDLE, PREFILL, DECODE = "idle", "prefill", "decode"


def _bucket(n: int) -> int:
    b = 8
    while b < n:
        b *= 2
    return b


def make_prefill_batch(cfg: ArchConfig, n_slots: int, admitted):
    """Assemble the padded prefill inputs for an admission wave:
    (batch dict, lengths [n_slots], slot_ids [n_slots]) with sentinel
    rows (id == n_slots) for padding — the cache writers drop them.
    Prompt lengths are bucketed to powers of two so jit's shape cache
    stays bounded. Shared by the engine and the draft-model proposer:
    the draft's cache frontier mirrors the target's only while the two
    prefill layouts stay identical, so there is exactly ONE builder."""
    s_pad = _bucket(max(r.prompt.size for _, r in admitted))
    if cfg.frontend == "vit":
        s_pad = max(s_pad, _bucket(cfg.frontend_len))
    tokens = np.zeros((n_slots, s_pad), np.int32)
    lengths = np.zeros(n_slots, np.int32)
    slot_ids = np.full(n_slots, n_slots, np.int32)
    for i, (slot, req) in enumerate(admitted):
        tokens[i, : req.prompt.size] = req.prompt
        lengths[i] = req.prompt.size
        slot_ids[i] = slot
    batch = {"tokens": jnp.asarray(tokens)}
    if cfg.frontend == "vit":
        # _embed_inputs overlays these onto the FIRST frontend_len prompt
        # positions (the model's VLM layout: those positions ARE the
        # image). Requests without patches get zeros — note that prompts
        # shorter than frontend_len are then fully covered by the (zero)
        # image prefix, as in training.
        patches = np.zeros((n_slots, cfg.frontend_len, cfg.frontend_dim),
                           np.float32)
        for i, (_, req) in enumerate(admitted):
            if req.patches is not None:
                patches[i] = req.patches
        batch["patches"] = jnp.asarray(patches)
    return batch, jnp.asarray(lengths), jnp.asarray(slot_ids)


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray          # [P] int32
    max_new: int
    arrival_step: int = 0
    # vit-frontend archs: image embeddings [frontend_len, frontend_dim]
    # overlaying the first frontend_len prompt positions (the model's
    # _embed_inputs semantics — those positions ARE the image). None ->
    # zeros (text-only synthetic serving).
    patches: Optional[np.ndarray] = None
    tokens: List[int] = dataclasses.field(default_factory=list)
    logits: List[np.ndarray] = dataclasses.field(default_factory=list)
    # models with experts: the experts each token fed through the model
    # was routed to, [n_moe_layers, rows, top_k] a prefill or decode call
    # (ids over all experts, in the router's order; not kept by the
    # speculative draft/verify step)
    routes: List[np.ndarray] = dataclasses.field(default_factory=list)
    done: bool = False


@dataclasses.dataclass
class EngineConfig:
    n_slots: int = 4
    max_len: int = 256
    block_size: int = 16
    backend: str = "paged"            # 'paged' | 'dense'
    prefill_mode: str = "batched"     # 'batched' | 'decode'
    # psum-sparsity sample period (decode steps between taps; 0 = off).
    # None -> ArchConfig.serve_telemetry_every. Every sample re-runs one
    # decode step with kernel_impl='xla' to materialize psums — keep it
    # sparse so steady-state steps skip the double compute.
    telemetry_every: Optional[int] = None
    record_logits: bool = False       # keep per-token logits (tests/bench)
    eos_token: Optional[int] = None
    n_blocks: Optional[Dict[str, int]] = None  # paged pool sizes (per kind)
    # Speculative decoding: K > 0 turns each decode iteration into a
    # draft/verify step — a proposer offers K tokens per slot, the target
    # scores all K+1 positions in ONE multi-token decode_step_spec call,
    # and the longest draft prefix matching the target's own greedy
    # continuations is committed (plus the bonus token). Greedy-exact:
    # committed streams are bit-identical to spec_tokens=0 for ANY
    # proposer (CI-gated). Paged backend + batched prefill only.
    spec_tokens: int = 0
    spec_draft: str = "ngram"         # 'ngram' | 'model' (serve.speculative)


class ServeEngine:
    def __init__(self, cfg: ArchConfig, params, ecfg: EngineConfig):
        if not cfg.supports_decode():
            raise ValueError(f"{cfg.name} is encoder-only: no decode step")
        if ecfg.prefill_mode not in ("batched", "decode"):
            raise ValueError(f"bad prefill_mode {ecfg.prefill_mode!r}")
        if cfg.frontend == "vit" and ecfg.prefill_mode == "decode":
            raise ValueError("vit-frontend archs need prefill_mode='batched'")
        if ecfg.spec_tokens and ecfg.prefill_mode != "batched":
            # decode-mode prefill would interleave prompt tokens with
            # drafts inside one multi-token append
            raise ValueError("speculative decoding needs batched prefill")
        self.cfg = cfg
        self.ecfg = ecfg
        # kept in the dtype the programs compute with: cast once here,
        # not inside every prefill and decode call
        self.params, self._cast_bytes = backends_lib.compute_weights(
            params, cfg)
        self.telemetry_every = (cfg.serve_telemetry_every
                                if ecfg.telemetry_every is None
                                else ecfg.telemetry_every)
        self.backend = backends_lib.make_backend(
            ecfg.backend, cfg, ecfg.n_slots, ecfg.max_len,
            ecfg.block_size, ecfg.n_blocks, ecfg.spec_tokens)
        self.proposer = None
        if ecfg.spec_tokens:
            from repro.serve import speculative as spec_lib
            self.proposer = spec_lib.make_proposer(
                ecfg.spec_draft, ecfg.spec_tokens, cfg, ecfg.n_slots,
                ecfg.max_len)
        self.caches = self.backend.init_caches()
        self.tables: Optional[BlockTables] = None
        if ecfg.backend == "paged":
            self.tables = BlockTables(
                ecfg.n_slots, self.backend.blocks_per_slot,
                self.backend.n_blocks)
        self.telemetry = self._new_telemetry()

        n = ecfg.n_slots
        self.slot_req: List[Optional[Request]] = [None] * n
        self.slot_phase = [IDLE] * n
        self.slot_pos = np.zeros(n, np.int32)
        self.slot_last = np.zeros(n, np.int32)
        self.slot_uses = np.zeros(n, np.int64)  # admissions per slot

        self.queue: deque[Request] = deque()
        self.results: Dict[int, Request] = {}
        self._next_rid = 0
        self._it = 0
        # jit's own shape-keyed cache handles per-bucket retraces; the
        # _bucket padding just bounds how many shapes it ever sees
        self._prefill_fn = jax.jit(steps_lib.make_batched_prefill_step(cfg))
        self._stats_fn = None
        self._dev_tables_cache = {}

    # ------------------------------------------------------------------
    # submission
    # ------------------------------------------------------------------

    def submit(self, prompt: np.ndarray, max_new: int, *,
               arrival_step: int = 0, rid: Optional[int] = None,
               patches: Optional[np.ndarray] = None) -> int:
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("empty prompt")
        if prompt.size + max_new > self.ecfg.max_len:
            raise ValueError(
                f"prompt ({prompt.size}) + max_new ({max_new}) exceeds "
                f"max_len={self.ecfg.max_len}")
        if patches is not None:
            want = (self.cfg.frontend_len, self.cfg.frontend_dim)
            if self.cfg.frontend != "vit":
                raise ValueError(f"{self.cfg.name} takes no patches")
            if tuple(np.shape(patches)) != want:
                raise ValueError(f"patches must be {want}")
            if prompt.size < self.cfg.frontend_len:
                # the image occupies positions 0..frontend_len-1; a
                # shorter prompt would cache (and attend) a truncated
                # image without any error surfacing
                raise ValueError(
                    f"vit prompts must span the image prefix: need "
                    f">= frontend_len={self.cfg.frontend_len} tokens, "
                    f"got {prompt.size}")
        if rid is None:
            rid = self._next_rid
        elif (rid in self.results
              or any(r.rid == rid for r in self.queue)
              or any(r is not None and r.rid == rid for r in self.slot_req)):
            raise ValueError(f"rid {rid} already in use")
        self._next_rid = max(self._next_rid, rid) + 1
        req = Request(rid=rid, prompt=prompt, max_new=max_new,
                      arrival_step=arrival_step, patches=patches)
        # keep FIFO-by-arrival; re-sort only on out-of-order submission
        # (workload generators already emit in arrival order)
        out_of_order = bool(self.queue) and (
            (self.queue[-1].arrival_step, self.queue[-1].rid)
            > (arrival_step, rid))
        self.queue.append(req)
        if out_of_order:
            self.queue = deque(sorted(
                self.queue, key=lambda r: (r.arrival_step, r.rid)))
        return rid

    # ------------------------------------------------------------------
    # main loop
    # ------------------------------------------------------------------

    def has_work(self) -> bool:
        return bool(self.queue) or any(p != IDLE for p in self.slot_phase)

    def reset_metrics(self) -> None:
        """Restart telemetry, results, the step clock and allocator
        diagnostics — call between a warmup run (which compiles every
        jitted program) and the measured run, so percentiles and the
        slot/block-reuse gates reflect serving, not compilation. The
        weight counters, fixed when the engine was built, carry over. The
        engine must be drained (no queued or active requests)."""
        if self.has_work():
            raise RuntimeError("reset_metrics on a non-drained engine")
        self.telemetry = self._new_telemetry()
        self.results = {}
        self._it = 0
        self.slot_uses[:] = 0
        if self.tables is not None:
            self.tables.reset_stats()

    def _new_telemetry(self) -> Telemetry:
        tel = Telemetry()
        tel.record_weights(self._cast_bytes,
                           backends_lib.weight_bytes(self.params))
        return tel

    def run(self, workload: Optional[Sequence[Tuple[int, np.ndarray, int]]]
            = None, *, max_steps: int = 100_000) -> Dict[str, Any]:
        """Drain `workload` [(arrival_step, prompt, max_new)] (plus
        anything already submitted) and return the telemetry summary."""
        for arrival, prompt, max_new in (workload or []):
            self.submit(prompt, max_new, arrival_step=arrival)
        steps = 0
        while self.has_work():
            self.step()
            steps += 1
            if steps > max_steps:
                raise RuntimeError(f"engine did not drain in {max_steps} steps")
        summary = self.telemetry.summary()
        summary["slot_uses"] = self.slot_uses.tolist()
        # sampling rate of the psum probe (each sample doubles one decode
        # step's compute; steady-state steps in between skip it entirely)
        summary["telemetry_sample_every"] = self.telemetry_every
        if self.tables is not None:
            summary["blocks"] = self.tables.stats()
        return summary

    def step(self) -> None:
        it = self._it
        self._it += 1
        now = self.telemetry.now()
        for req in self.queue:  # sorted by arrival: stop at the future
            if req.arrival_step > it:
                break
            trace = self.telemetry.trace(req.rid)
            if trace.arrival_wall is None:
                trace.arrival_wall = now

        admitted = self._admit(it)
        if admitted:
            mask = np.zeros(self.ecfg.n_slots, bool)
            for slot, _ in admitted:
                mask[slot] = True
            # recurrent slots restart from their init state; stale KV
            # needs no reset (ring masking never reads it)
            self.caches = self.backend.reset_slots(self.caches,
                                                   jnp.asarray(mask))
            if self.ecfg.prefill_mode == "batched":
                self._batched_prefill(admitted)
            if self.proposer is not None:
                self.proposer.on_admit(admitted)

        if not any(p != IDLE for p in self.slot_phase):
            return

        if self.telemetry_every and it % self.telemetry_every == 0:
            self._sample_sparsity()
        if self.ecfg.spec_tokens:
            self._spec_decode_step()
        else:
            self._decode_step()

    # ------------------------------------------------------------------
    # admission / eviction
    # ------------------------------------------------------------------

    def _admit(self, it: int) -> List[Tuple[int, Request]]:
        admitted = []
        while self.queue and self.queue[0].arrival_step <= it:
            try:
                slot = self.slot_phase.index(IDLE)
            except ValueError:
                break
            if self.tables is not None and not self.tables.assign(slot):
                break  # pool exhausted: head-of-line waits for an eviction
            req = self.queue.popleft()
            self.slot_req[slot] = req
            self.slot_pos[slot] = 0
            self.slot_last[slot] = req.prompt[0]
            self.slot_phase[slot] = PREFILL
            self.slot_uses[slot] += 1
            admitted.append((slot, req))
            self._dev_tables_cache = {}  # tables changed -> re-upload
        return admitted

    def _evict(self, slot: int) -> None:
        req = self.slot_req[slot]
        trace = self.telemetry.trace(req.rid)
        trace.finish_wall = self.telemetry.now()
        trace.n_generated = len(req.tokens)
        req.done = True
        self.results[req.rid] = req
        self.slot_req[slot] = None
        self.slot_phase[slot] = IDLE
        if self.tables is not None:
            self.tables.release(slot)
            self._dev_tables_cache = {}

    def _maybe_finish(self, slot: int) -> None:
        req = self.slot_req[slot]
        eos = (self.ecfg.eos_token is not None and req.tokens
               and req.tokens[-1] == self.ecfg.eos_token)
        out_of_room = self.slot_pos[slot] >= self.ecfg.max_len
        if len(req.tokens) >= req.max_new or eos or out_of_room:
            self._evict(slot)

    # ------------------------------------------------------------------
    # prefill
    # ------------------------------------------------------------------

    def _device_tables(self, covered: Optional[Dict[str, int]] = None):
        """Device block tables, optionally sliced to the covered-prefix
        block count per kind (dead-block skipping: blocks no slot position
        can reach are dropped from the decode program entirely — the XLA
        twin of the fused kernel's pl.when chunk skip). Uploads are cached
        per prefix shape and invalidated on any table change."""
        if self.tables is None:
            return None
        key = (None if covered is None
               else tuple(sorted(covered.items())))
        hit = self._dev_tables_cache.get(key)
        if hit is None:
            hit = {
                k: (jnp.asarray(v) if covered is None
                    else jnp.asarray(v[:, : covered[k]]))
                for k, v in self.tables.tables.items()
            }
            self._dev_tables_cache[key] = hit
        return hit

    def _batched_prefill(self, admitted: List[Tuple[int, Request]]) -> None:
        batch, lengths, slot_ids = make_prefill_batch(
            self.cfg, self.ecfg.n_slots, admitted)

        t0 = time.perf_counter()
        first, last, contribs, routed = self._prefill_fn(
            self.params, batch, lengths)
        self.caches = self.backend.write_prefill(
            self.caches, contribs, slot_ids, lengths, self._device_tables())
        first_np, routed_np = jax.device_get((first, routed))
        last_np = np.asarray(last) if self.ecfg.record_logits else None
        self.telemetry.record_prefill(time.perf_counter() - t0)
        self.telemetry.record_routed("prefill", routed_np)
        top = None
        if routed_np:
            top = routed_np["top"]
            top = top.reshape(top.shape[0], self.ecfg.n_slots, -1,
                              top.shape[-1])

        now = self.telemetry.now()
        for i, (slot, req) in enumerate(admitted):
            if top is not None:
                req.routes.append(top[:, i, : req.prompt.size].copy())
            tok = int(first_np[i])
            req.tokens.append(tok)
            if last_np is not None:
                req.logits.append(last_np[i])
            trace = self.telemetry.trace(req.rid)
            trace.first_token_wall = now
            if trace.arrival_wall is None:
                trace.arrival_wall = now
            self.slot_pos[slot] = req.prompt.size
            self.slot_last[slot] = tok
            self.slot_phase[slot] = DECODE
            self._maybe_finish(slot)

    # ------------------------------------------------------------------
    # decode
    # ------------------------------------------------------------------

    def _decode_step(self) -> None:
        n = self.ecfg.n_slots
        tokens = np.zeros(n, np.int32)
        for s in range(n):
            if self.slot_phase[s] == DECODE:
                tokens[s] = self.slot_last[s]
            elif self.slot_phase[s] == PREFILL:
                tokens[s] = self.slot_req[s].prompt[self.slot_pos[s]]
        positions = self.slot_pos.copy()

        # dead-block skipping: the host knows every slot's position, so
        # blocks past the covered prefix are provably unread — hand the
        # decode program tables sliced to that prefix (bucketed; the
        # fused kernel additionally pl.when-skips per (slot, block))
        covered = None
        if self.tables is not None:
            active = [int(positions[s]) for s in range(n)
                      if self.slot_phase[s] != IDLE]
            covered = self.backend.covered_blocks(max(active, default=0))
        # table upload is admission-time bookkeeping (cached until the
        # allocator changes) — keep it out of the measured decode step
        dev_tables = self._device_tables(covered)

        t0 = time.perf_counter()
        nxt, logits, self.caches = self.backend.decode(
            self.params, self.caches, dev_tables,
            jnp.asarray(tokens), jnp.asarray(positions))
        nxt_np, routed_np = jax.device_get((nxt, self.backend.routed))
        logits_np = np.asarray(logits) if self.ecfg.record_logits else None
        dt = time.perf_counter() - t0
        self.telemetry.record_routed("decode", routed_np)
        top = routed_np["top"] if routed_np else None

        emitted = 0
        now = self.telemetry.now()
        for s in range(n):
            req = self.slot_req[s]
            if top is not None and self.slot_phase[s] != IDLE:
                req.routes.append(top[:, s : s + 1])
            if self.slot_phase[s] == DECODE:
                tok = int(nxt_np[s])
                req.tokens.append(tok)
                if logits_np is not None:
                    req.logits.append(logits_np[s])
                self.slot_last[s] = tok
                self.slot_pos[s] += 1
                emitted += 1
                self._maybe_finish(s)
            elif self.slot_phase[s] == PREFILL:
                self.slot_pos[s] += 1
                if self.slot_pos[s] == req.prompt.size:
                    tok = int(nxt_np[s])
                    req.tokens.append(tok)
                    if logits_np is not None:
                        req.logits.append(logits_np[s])
                    trace = self.telemetry.trace(req.rid)
                    trace.first_token_wall = now
                    if trace.arrival_wall is None:
                        trace.arrival_wall = now
                    self.slot_last[s] = tok
                    self.slot_phase[s] = DECODE
                    emitted += 1
                    self._maybe_finish(s)
        self.telemetry.record_step(dt, emitted)

    # ------------------------------------------------------------------
    # speculative decode (draft / verify)
    # ------------------------------------------------------------------

    def _spec_decode_step(self) -> None:
        """One draft/verify iteration: K proposer drafts per active slot,
        ONE multi-token decode_step_spec over all K+1 positions, commit
        the longest draft prefix matching the target's greedy
        continuations + the bonus token. Every slot advances by its own
        acceptance count (variable-advance position vectors); commits are
        capped at max_new / truncated at eos, so a slot can finish — and
        be evicted — mid-draft with rejected-draft state left behind
        (harmless: KV self-heals, recurrent rows reset at admission)."""
        n, k = self.ecfg.n_slots, self.ecfg.spec_tokens
        active = np.array([p == DECODE for p in self.slot_phase])
        histories: List[Optional[np.ndarray]] = [None] * n
        for s in range(n):
            if active[s]:
                req = self.slot_req[s]
                histories[s] = np.concatenate(
                    [req.prompt, np.asarray(req.tokens, np.int32)])
        # drafting is PART of the measured step — a draft-model proposer
        # pays K extra decode steps here and the spec-vs-baseline
        # throughput comparison must charge for them (dt accumulates
        # propose + verify + frontier-advance below)
        t0 = time.perf_counter()
        drafts = self.proposer.propose(active, histories)
        dt = time.perf_counter() - t0

        tokens = np.zeros((n, k + 1), np.int32)
        for s in range(n):
            if active[s]:
                tokens[s, 0] = self.slot_last[s]
                tokens[s, 1:] = drafts[s]
        positions = self.slot_pos.copy()

        covered = None
        if self.tables is not None:
            # the append writes (and its q-tokens may attend) up to
            # position base + k — cover the drafts, not just the base
            act_pos = [int(positions[s]) for s in range(n) if active[s]]
            covered = self.backend.covered_blocks(
                max(act_pos, default=0) + k)
        dev_tables = self._device_tables(covered)

        t0 = time.perf_counter()
        greedy, logits, keep, self.caches = self.backend.decode_spec(
            self.params, self.caches, dev_tables,
            jnp.asarray(tokens), jnp.asarray(positions))
        greedy_np = np.asarray(greedy)
        keep_np = np.asarray(keep)
        logits_np = np.asarray(logits) if self.ecfg.record_logits else None
        dt += time.perf_counter() - t0

        emitted = accepted = 0
        committed: List[Optional[np.ndarray]] = [None] * n
        for s in range(n):
            if not active[s]:
                continue
            req = self.slot_req[s]
            accepted += int(keep_np[s]) - 1
            c = min(int(keep_np[s]), req.max_new - len(req.tokens))
            toks = greedy_np[s, :c]
            if self.ecfg.eos_token is not None:
                hits = np.flatnonzero(toks == self.ecfg.eos_token)
                if hits.size:
                    c = int(hits[0]) + 1
                    toks = toks[:c]
            committed[s] = toks
            req.tokens.extend(int(t) for t in toks)
            if logits_np is not None:
                req.logits.extend(logits_np[s, i] for i in range(c))
            self.slot_last[s] = int(toks[-1])
            self.slot_pos[s] += c
            emitted += c
        t0 = time.perf_counter()
        self.proposer.on_commit(committed)
        dt += time.perf_counter() - t0
        n_active = int(active.sum())
        self.telemetry.record_step(dt, emitted)
        self.telemetry.record_spec(n_active * k, accepted, emitted, n_active)
        for s in range(n):
            if active[s]:
                self._maybe_finish(s)

    # ------------------------------------------------------------------
    # telemetry probe
    # ------------------------------------------------------------------

    def _sample_sparsity(self) -> None:
        if self.cfg.linear_impl != "cadc":
            return
        if self._stats_fn is None:
            cfg = self.cfg
            # psums only materialize on the XLA linears; the gather
            # attention path keeps the probe cheap and backend-agnostic
            ucfg = cfg.with_overrides(scan_layers=False, kernel_impl="xla",
                                      paged_attn_impl="xla")
            paged = self.ecfg.backend == "paged"
            ring_lens = self.backend.ring_len if paged else None

            def stats(params, caches, tables, tokens, positions):
                # unstacked IN-trace (like the caches): no persistent
                # 2x-params copy lives on device for telemetry's sake
                params_u = tf.unstack_tree(params, cfg)
                caches_u = tf.unstack_tree(caches, cfg)
                with ll.psum_stats_tap() as tap:
                    if paged:
                        tf.decode_step_paged(
                            cast_compute(params_u, ucfg), tokens, positions,
                            caches_u, tables, ucfg, ring_lens=ring_lens)
                    else:
                        tf.decode_step(
                            cast_compute(params_u, ucfg), tokens, positions,
                            caches_u, ucfg)
                    recs = list(tap)
                return {
                    r["label"]: {"gate_off": r["gate_off"],
                                 "exact_zero": r["exact_zero"],
                                 "segments": r["segments"]}
                    for r in recs
                }

            self._stats_fn = jax.jit(stats)

        n = self.ecfg.n_slots
        tokens = np.array(
            [self.slot_last[s] if self.slot_phase[s] != IDLE else 0
             for s in range(n)], np.int32)
        out = self._stats_fn(self.params, self.caches,
                             self._device_tables(), jnp.asarray(tokens),
                             jnp.asarray(self.slot_pos))
        self.telemetry.record_sparsity(
            {k: {kk: np.asarray(vv) for kk, vv in v.items()}
             for k, v in out.items()})
