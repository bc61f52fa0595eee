"""Driver of the lfm2 serving cell: LFM2-8B-A1B (this chip's share of its
experts) through the program's `ServeEngine`, as `drivers/serve.py` drives
phi4: continuous batching, the paged backend, the batched prefill, one
schedule in wall time, the served tokens compared with the plain
reference (`reference/lfm2_moe_decoder.py`).

What differs from `drivers/serve.py`: the configuration's keys and the
weights' layout (leading dense layers, then the MoE layers; the held
experts' weights), routers and the tied table whitened against the
residual stream's common offset (`whiten_routers`, `whiten_head`), the
reference, and the counter: every prefill and decode call's rows routed
to each held expert in each MoE layer (`Telemetry.routed`) goes into the
step's record, and a traced run keeps each engine step's span so that a
kernel's time can be read per step.

The check follows the program's own routing: the reference takes the
experts the program selected for each token it computed (`Request.
routes`), and counts where its own float32 choice differs, under a limit
of its own (`route_flip_share`). With the control on, the control is
checked the same way against its own selection, and the check also reads
how far bfloat16 rounding alone moves a logit and flips a choice.
"""
from __future__ import annotations

import dataclasses
import gc
import time
from typing import Any, Dict, List

import numpy as np

import session
import trace_reduce
from drivers.serve import Tracker, reduce_trace, sample, warm_requests, \
    widest_gap
from traffic import generator

KINDS = {"conv": "conv", "full_attention": "global"}


def program_config(cfg: Dict):
    """The program's ArchConfig for the configuration file, checked
    against the file's sizes and the deployment's share of experts."""
    from repro.configs import get_config

    prog = cfg["program"]
    arch = get_config(prog["arch"], **prog["overrides"])
    arch = arch.with_overrides(moe=dataclasses.replace(arch.moe,
                                                       **prog["moe"]))
    n = cfg["num_hidden_layers"]
    got = {
        "num_hidden_layers": arch.n_layers,
        "hidden_size": arch.d_model,
        "num_attention_heads": arch.n_heads,
        "num_key_value_heads": arch.n_kv_heads,
        "intermediate_size": arch.d_ff,
        "moe_intermediate_size": arch.moe.d_expert,
        "num_experts_per_tok": arch.moe.top_k,
        "num_experts": arch.moe.held,
        "first_expert": arch.moe.first_expert,
        "num_dense_layers": arch.n_dense_layers,
        "conv_L_cache": arch.conv1d_width,
        "vocab_size": arch.vocab_size,
        "rope_theta": arch.rope_theta,
        "norm_eps": arch.norm_eps,
        "tie_word_embeddings": arch.tie_embeddings,
        "use_expert_bias": arch.moe.expert_bias,
        "norm_topk_prob": True,
        "routed_scaling_factor": 1,
        "crossbar_size": arch.crossbar_size,
        "dendritic_fn": arch.dendritic_fn,
        "layer_types": list(arch.pattern_for_layers),
    }
    want = dict(cfg, layer_types=[KINDS[k] for k in cfg["layer_types"][:n]])
    for k, v in got.items():
        if v != want[k]:
            raise ValueError(f"program {k}={v!r} but the configuration "
                             f"says {want[k]!r}")
    if arch.moe.n_experts != cfg["published"]["num_experts"] or \
            arch.head_dim * arch.n_heads != arch.d_model or \
            arch.moe.score != "sigmoid" or not arch.qk_norm:
        raise ValueError("program router, heads or QK-norm differ from the "
                         "configuration")
    return arch


def make_weights(arch, seed: int):
    """Weights in the program's layout, float32 as the program serves
    them, from the seed in one jitted call (the scales of `assumed`):
    CADC linears and experts normal with std 1/sqrt(fan-in), router
    1/sqrt(d) (before `whiten_routers`), conv taps 1/3, expert bias 0.05,
    layer and QK norm scales 0.1-normal around the stored (1 + scale), the
    final norm's 1, and embedding rows std 0.02 less their mean (as
    `drivers/serve.py` makes phi4's, for the reason given there)."""
    import jax
    import jax.numpy as jnp

    from repro.models.lm import transformer as tf

    shapes = jax.eval_shape(lambda k: tf.init(k, arch), jax.random.PRNGKey(0))
    paths, tree = jax.tree_util.tree_flatten_with_path(shapes)
    d = arch.d_model

    def leaf(key, path, s):
        names = [str(getattr(e, "key", getattr(e, "name", e)))
                 for e in path]
        z = jax.random.normal(key, s.shape, jnp.float32)
        if names[-1] == "table":
            t = 0.02 * z
            return t - t.mean(axis=1, keepdims=True)
        if names[-1] == "scale":
            return jnp.zeros(s.shape) if names[0] == "final_norm" \
                else 0.1 * z
        if names[-1] == "router":
            return z * d ** -0.5
        if names[-1] == "expert_bias":
            return 0.05 * z
        if names[-2:] == ["conv", "w"]:
            return z / arch.conv1d_width
        # CADC linear or expert weight [..., S, xbar, N]: every width here
        # is a whole number of crossbars, so no row is padding
        return z / (s.shape[-3] * s.shape[-2]) ** 0.5

    def make(key):
        return jax.tree_util.tree_unflatten(tree, [
            leaf(jax.random.fold_in(key, i), p, s)
            for i, (p, s) in enumerate(paths)])

    return jax.jit(make)(session.key(seed))


def whiten_routers(params, fwd, tokens, n_moe: int, top_k: int):
    """The routers made to read what varies from token to token. Every
    CADC linear adds y = sum_s relu(x_s @ w_s) >= 0 to the residual stream,
    an offset the same at every token that random routers read as the
    same few experts for every token. Layer by layer, in order (a layer's
    input depends on the routers before it), the reference's float32
    forward `fwd` over `tokens` gives the MoE layer's input h; the router
    loses h's mean direction and each expert's column is scaled to unit
    std over the tokens. Returns the weights with the new routers."""
    import jax
    import jax.numpy as jnp

    if params.get("units"):
        raise ValueError("routers are whitened in a stack without scanned "
                         "units")
    hi = jax.lax.Precision.HIGHEST

    @jax.jit
    def whiten(w, h):
        c = jnp.mean(h, axis=0)
        c = c / jnp.linalg.norm(c)
        w = w - c[:, None] * jnp.dot(c, w, precision=hi)[None, :]
        return w / jnp.std(jnp.dot(h, w, precision=hi), axis=0,
                           keepdims=True)

    own = -jnp.ones((n_moe, tokens.shape[0], top_k), jnp.int32)
    tail = list(params["tail"])
    moe = [i for i, lp in enumerate(tail) if "moe" in lp]
    if len(moe) != n_moe:
        raise ValueError(f"{len(moe)} MoE layers after the leading ones, "
                         f"the configuration has {n_moe}")
    for j, i in enumerate(moe):
        h = fwd(params, tokens, own)[3][j]
        tail[i] = dict(tail[i], moe=dict(
            tail[i]["moe"], router=whiten(tail[i]["moe"]["router"], h)))
        params = dict(params, tail=tuple(tail))
    return params


def whiten_head(params, fwd, tokens, n_moe: int, top_k: int):
    """The tied table made to read what varies from token to token: the
    final norm's output carries the CADC linears' common offset too, which
    random table rows read as the same few tokens at every position (greedy
    decoding then feeds every slot the same tokens, and decode reaches few
    experts). Every row loses the mean direction of the final stream over
    `tokens` (the reference's float32 forward `fwd`). The table is also
    the embedding; a row loses about 1/sqrt(d) of its norm there."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST

    @jax.jit
    def whiten(t, x):
        c = jnp.mean(x, axis=0)
        c = c / jnp.linalg.norm(c)
        return t - jnp.dot(t, c, precision=hi)[:, None] * c[None, :]

    own = -jnp.ones((n_moe, tokens.shape[0], top_k), jnp.int32)
    x = fwd(params, tokens, own)[4]
    embed = params["embed"]
    return dict(params, embed=dict(embed, table=whiten(embed["table"], x)))


def run(ctx) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp

    from repro.serve import EngineConfig, ServeEngine

    from reference import lfm2_moe_decoder as ref

    cfg, mix = ctx.config, ctx.mix
    arch = program_config(cfg)
    n_moe = cfg["num_hidden_layers"] - cfg["num_dense_layers"]
    k = cfg["num_experts_per_tok"]
    ref_cfg = dict(cfg, first_expert=arch.moe.first_expert)
    ref_fn = ref.logits_fn(ref_cfg)
    seq = jnp.asarray(generator.rng(ctx.seed, 11).integers(
        0, cfg["vocab_size"], mix["max_len"]), jnp.int32)
    params = whiten_routers(make_weights(arch, ctx.seed), ref_fn, seq,
                            n_moe, k)
    params = whiten_head(params, ref_fn, seq, n_moe, k)
    e = dict(cfg["engine"], **mix.get("engine", {}))
    engine = ServeEngine(arch, params, EngineConfig(
        n_slots=e["n_slots"], max_len=mix["max_len"],
        block_size=e["block_size"], backend=e["backend"],
        prefill_mode=e["prefill_mode"], telemetry_every=0,
        record_logits=False))
    warm_gen = generator.rng(ctx.seed, 5)
    for plen, new in warm_requests(mix, e["block_size"]):
        engine.submit(warm_gen.integers(0, cfg["vocab_size"], plen), new)
        while engine.has_work():
            engine.step()
    engine.reset_metrics()
    sched = generator.schedule(mix, ctx.seed, ctx.seconds, cfg["vocab_size"])
    setup_s = time.perf_counter() - ctx.t_start
    ctx.log(f"set-up {setup_s:.3f} s: {len(sched)} requests scheduled")

    track = Tracker()
    prof = session.Profiler() if ctx.trace else None
    t_a, t_b = session.trace_span(ctx.seconds)
    tracing = None
    n_compiles = ctx.compiles()
    nxt, late = 0, 0.0
    tel = engine.telemetry
    t0 = time.perf_counter()
    t_end = t0 + ctx.seconds
    drain_end = t_end + mix.get("drain_s", 0)
    while True:
        now = time.perf_counter()
        if now >= t_end:
            due_done = all(r["req"].done for r in track.reqs.values())
            if now >= drain_end or due_done or not mix.get("drain_s"):
                break
        if prof is not None and tracing is None and now - t0 >= t_a:
            prof.start()
            tracing, trace_from = True, len(track.steps)
        if tracing and now - t0 >= t_b:
            trace_to = len(track.steps)
            events = prof.stop()
            tracing = False
        while now < t_end and nxt < len(sched) and \
                t0 + sched.arrival_s[nxt] <= now:
            late = max(late, now - t0 - sched.arrival_s[nxt])
            engine.submit(sched.prompts[nxt], int(sched.max_new[nxt]),
                          rid=nxt)
            track.add(nxt, engine.queue[-1], t0 + sched.arrival_s[nxt])
            nxt += 1
        if engine.has_work():
            n_pre, n_dec = len(tel.prefill_s), len(tel.step_s)
            n_routed = len(tel.routed)
            a = time.perf_counter()
            if tracing:
                with jax.profiler.TraceAnnotation("bench.step"):
                    engine.step()
            else:
                engine.step()
            track.after_step(a, time.perf_counter(), tel, n_pre, n_dec)
            track.steps[-1]["routed"] = [
                [kind, rows.tolist()] for kind, rows in tel.routed[n_routed:]]
        elif now < t_end:
            wait = (t0 + sched.arrival_s[nxt] if nxt < len(sched)
                    else t_end) - now
            if tracing:
                with jax.profiler.TraceAnnotation("bench.wait"):
                    time.sleep(max(0.0, min(wait, t_end - now)))
            else:
                time.sleep(max(0.0, min(wait, t_end - now)))
    if tracing:
        trace_to = len(track.steps)
        events = prof.stop()
    n_compiles = ctx.compiles() - n_compiles
    ctx.log(f"generator ran at most {late * 1e3:.3f} ms late; "
            f"{nxt} requests submitted in the window")
    mem = session.memory_peak_bytes()

    reqs = track.reqs
    in_window = [r for r in reqs.values() if r["due"] < t_end]
    attempted = sum(1 for r in in_window if r["times"])
    queued = sum(1 for r in in_window if not r["req"].done)
    ctx.log(f"{attempted} requests served, {queued} unfinished at the end")
    dec = [np.asarray(rows) for st in track.steps for kind, rows in
           st["routed"] if kind == "decode"]
    if dec:
        ctx.log(f"held experts with rows, a decode call and MoE layer: "
                f"{np.mean([np.sum(d > 0, axis=1) for d in dec]):.3f} of "
                f"{dec[0].shape[1]}; rows a held expert "
                f"{np.mean(dec):.3f}")
    rec: Dict[str, Any] = {
        "kind": "serve", "traffic": ctx.cell["traffic"],
        "seconds": ctx.seconds, "setup_s": setup_s, "late_s": late,
        "t0": t0, "t_end": t_end,
        "attempted": attempted, "failed": 0,
        "compiles_in_window": n_compiles, "memory_peak_bytes": mem,
        "requests": [{"due": r["due"], "prompt": r["prompt"],
                      "times": r["times"]} for r in reqs.values()],
        "steps": [s for s in track.steps if s["a"] < t_end],
        "config": cfg, "mix": mix, "n_slots": e["n_slots"],
    }
    if prof is not None:
        traced = track.steps[trace_from:trace_to]
        rec["trace"] = reduce_trace(events, traced)
        rec["trace"]["step_spans"] = step_spans(events, len(traced))

    # correctness: served tokens of a sample of finished requests, the
    # reference following the program's own routing
    done = [r for r in reqs.values() if r["req"].done and r["req"].tokens]
    del engine, track
    gc.collect()
    pick = sample(done, generator.rng(ctx.seed, 9), mix["check_requests"])
    ctl_fn = ref.logits_fn(ref_cfg, quant=cfg["control"]) if ctx.control \
        else None
    b16_fn = ref.logits_fn(ref_cfg, quant="bf16") if ctx.control else None
    unforced = -np.ones((n_moe, mix["max_len"], k), np.int32)
    worst = ctl_worst = noise = 0.0
    n_tok, flip = 0, Flips()
    ctl_flip, b16_flip = Flips(), Flips()
    for r in pick:
        req = r["req"]
        toks = np.zeros(mix["max_len"], np.int32)
        seq = np.concatenate([req.prompt, np.asarray(req.tokens, np.int32)])
        toks[:seq.size] = seq
        toks = jnp.asarray(toks)
        # every token the program computed: the prompt and all served
        # tokens but the last
        seen = seq.size - 1
        prog = np.concatenate(req.routes, axis=1)
        if prog.shape[1] != seen:
            raise ValueError(f"{prog.shape[1]} routed rows for {seen} "
                             f"computed tokens")
        forced = unforced.copy()
        forced[:, :seen] = np.sort(prog, axis=-1)
        logits, own, margin = ref_fn(params, toks, jnp.asarray(forced))[:3]
        logits = np.asarray(logits)
        worst = max(worst, widest_gap(logits, req.prompt.size, req.tokens))
        flip.add(forced, own, margin, seen)
        n_tok += len(req.tokens)
        if ctl_fn is not None:
            rows = slice(req.prompt.size - 1, seen)
            # the control in the program's place: its own routing and its
            # own tokens, against the reference that follows its routing
            c_logits, c_sel = ctl_fn(params, toks, jnp.asarray(unforced))[:2]
            c_ref, c_own, c_margin = ref_fn(params, toks, c_sel)[:3]
            ctl_worst = max(ctl_worst, widest_gap(
                np.asarray(c_ref), req.prompt.size,
                np.argmax(np.asarray(c_logits)[rows], axis=1)))
            ctl_flip.add(np.asarray(c_sel), c_own, c_margin, seen)
            b_logits, b_sel = b16_fn(params, toks, jnp.asarray(unforced))[:2]
            b_ref, b_own, b_margin = ref_fn(params, toks, b_sel)[:3]
            noise = max(noise, float(np.max(np.abs(
                np.asarray(b_logits)[rows] - np.asarray(b_ref)[rows]))))
            b16_flip.add(np.asarray(b_sel), b_own, b_margin, seen)
    ctx.log(f"checked {n_tok} served tokens of {len(pick)} finished "
            f"requests against the reference; {flip.n} of {flip.pairs} "
            f"(token, MoE layer) routing choices differ from its own, the "
            f"widest at a float32 margin of {flip.margin:.6f}")
    rec["checks"] = {
        "token_logit_gap": session.check(
            worst if pick else float("inf"),
            cfg["limits"]["token_logit_gap"]),
        "route_flip_share": session.check(
            flip.share if flip.pairs else float("inf"),
            cfg["limits"]["route_flip_share"])}
    if ctl_fn is not None:
        # the control's readings of the program's checks, and what
        # bfloat16 rounding of every product's operands alone does: moves
        # a logit by up to bf16_logit_err and flips bf16_flip_share of
        # the routing choices, the widest at a float32 margin of
        # bf16_flip_margin
        rec["control"] = {"token_logit_gap": ctl_worst,
                          "route_flip_share": ctl_flip.share,
                          "bf16_logit_err": noise,
                          "bf16_flip_share": b16_flip.share,
                          "bf16_flip_margin": b16_flip.margin,
                          "route_pairs": flip.pairs}
    return rec


class Flips:
    """Routing choices of the checked tokens where the reference's own
    float32 choice differs from the one it was given, and the widest
    float32 margin (k-th minus (k+1)-th score) among those."""

    def __init__(self):
        self.n = self.pairs = 0
        self.margin = 0.0

    def add(self, given, own, margin, seen: int) -> None:
        given = np.sort(np.asarray(given)[:, :seen], axis=-1)
        differ = np.any(given != np.asarray(own)[:, :seen], axis=-1)
        self.n += int(differ.sum())
        self.pairs += int(differ.size)
        if differ.any():
            self.margin = max(self.margin, float(
                np.max(np.asarray(margin)[:, :seen][differ])))

    @property
    def share(self) -> float:
        return self.n / max(self.pairs, 1)


def step_spans(events: Dict, n_steps: int) -> List[List[int]]:
    """Each traced engine step's `bench.step` span [start_ns, end_ns], in
    order; empty unless the trace holds one span a traced step."""
    spans = sorted([s, s + d] for n, s, d in events["host"]
                   if n == "bench.step")
    return spans if len(spans) == n_steps else []


def kernel_ns_per_step(t: Dict, kernel: str) -> List[int]:
    """The kernel's summed event time inside each traced step's span."""
    return [trace_reduce.kernel_ns(t["device"], [tuple(s)], kernel)[0]
            for s in t.get("step_spans", [])]
