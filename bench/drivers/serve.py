"""Driver of the serving cells: requests through the program's
`ServeEngine` (continuous batching, paged KV cache, batched prefill), open
loop in wall time.

Set-up makes the weights on the device from the seed in one jitted call,
builds the engine, and sends one request for every prompt-length bucket
and every decode-table bucket the mix can reach, so that the window runs
only compiled programs. The window submits each request when it is due,
drives `engine.step()` whenever the engine has work, and stamps every
output token with the end of the step that emitted it. A mix with a drain
keeps stepping after the window, without new arrivals, until every
request due in the window has finished. A sample of finished requests,
drawn from the seed, is then compared with the plain reference: at each
served token, how far its logit lies below the reference's best.
"""
from __future__ import annotations

import gc
import time
from typing import Any, Dict, List

import numpy as np

import flops
import session
import trace_reduce
from traffic import generator


def program_config(cfg: Dict):
    """The program's ArchConfig for a serving configuration file, checked
    against the file's published sizes."""
    from repro.configs import get_config

    prog = cfg["program"]
    arch = get_config(prog["arch"], **prog["overrides"])
    want = {"n_layers": "num_hidden_layers", "d_model": "hidden_size",
            "n_heads": "num_attention_heads",
            "n_kv_heads": "num_key_value_heads", "head_dim": "head_dim",
            "d_ff": "intermediate_size", "vocab_size": "vocab_size",
            "tie_embeddings": "tie_word_embeddings",
            "rope_theta": "rope_theta", "norm_eps": "rms_norm_eps",
            "crossbar_size": "crossbar_size",
            "dendritic_fn": "dendritic_fn"}
    for field, k in want.items():
        if getattr(arch, field) != cfg[k]:
            raise ValueError(f"program {field}={getattr(arch, field)!r} but "
                             f"the configuration says {k}={cfg[k]!r}")
    return arch


def make_weights(arch, cfg: Dict, seed: int):
    """Weights in the program's layout (layers stacked, CADC linears as
    [segments, crossbar, out]), from the seed in one jitted call, float32
    as the program serves them: linears normal with std 1/sqrt(fan-in),
    layer norm scales 0.1-normal around the stored (1 + scale), the final
    norm's scale 1, and embedding rows normal with std 0.02 less their
    mean. Every CADC linear's output carries a positive offset, a sum of
    ReLU'd partial sums, that points the same way at every position and
    grows through the depth; a zero-mean row of the tied head is blind to
    it. Without that, the greedy token is the same at every position of
    every sequence, and the served-token comparison could tell no
    precision from another."""
    import jax
    import jax.numpy as jnp

    layers, d, xbar = cfg["num_hidden_layers"], cfg["hidden_size"], \
        cfg["crossbar_size"]

    def linear(k, d_in, d_out):
        s = -(-d_in // xbar)
        w = jax.random.normal(k, (layers, s * xbar, d_out)) / d_in ** 0.5
        w = jnp.where(jnp.arange(s * xbar)[None, :, None] < d_in, w, 0.0)
        return {"w": w.reshape(layers, s, xbar, d_out)}

    def make(key):
        ks = iter(jax.random.split(key, 16))
        names = ["wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"]
        lin = {n: linear(next(ks), a, b)
               for n, (a, b) in zip(names, flops.decoder_linears(cfg))}
        unit = {
            "ln1": {"scale": 0.1 * jax.random.normal(next(ks), (layers, d))},
            "attn": {n: lin[n] for n in names[:4]},
            "ln2": {"scale": 0.1 * jax.random.normal(next(ks), (layers, d))},
            "ffn": {n: lin[n] for n in names[4:]},
        }
        table = 0.02 * jax.random.normal(next(ks), (arch.padded_vocab, d))
        return {
            "embed": {"table": table - table.mean(axis=1, keepdims=True)},
            "final_norm": {"scale": jnp.zeros((d,))},
            "units": (unit,),
            "tail": (),
        }

    return jax.jit(make)(session.key(seed))


def warm_requests(mix: Dict, block: int) -> List[tuple]:
    """(prompt length, max_new) of the warm-up requests: every prefill
    bucket of the mix, and a decode at the first position of every
    power-of-two count of table blocks (the engine slices its block
    tables to such counts) that the mix's positions reach."""
    lo, hi, max_len = mix["prompt"]["min"], mix["prompt"]["max"], \
        mix["max_len"]
    positions = {lo} | {block * 2 ** j for j in range(32)
                        if lo < block * 2 ** j < max_len}
    out = {(p, 2) for p in generator.prefill_lengths(mix)}
    for p in positions:
        prompt = min(p, hi)
        out.add((prompt, p - prompt + 2))
    return sorted(out)


class Tracker:
    """The benchmark's own record of the window: when each request was
    due, when each of its tokens came out, and each step's span."""

    def __init__(self):
        self.reqs: Dict[int, Dict[str, Any]] = {}
        self.live: Dict[int, Any] = {}
        self.steps: List[Dict[str, Any]] = []

    def add(self, rid: int, req, due: float) -> None:
        self.reqs[rid] = {"due": due, "prompt": int(req.prompt.size),
                          "times": [], "req": req}
        self.live[rid] = req

    def after_step(self, a: float, b: float, tel, n_pre: int, n_dec: int
                   ) -> None:
        prefilled, decoded = [], []
        for rid, req in list(self.live.items()):
            r = self.reqs[rid]
            n_old, n_new = len(r["times"]), len(req.tokens)
            if n_new > n_old:
                if n_old == 0:
                    prefilled.append(r["prompt"])
                # a decode step input token at position prompt + k - 1
                # yields output token k (k >= 1)
                for k in range(max(n_old, 1), n_new):
                    decoded.append(r["prompt"] + k - 1)
                r["times"].extend([b] * (n_new - n_old))
            if req.done:
                del self.live[rid]
        self.steps.append({
            "a": a, "b": b,
            "prefill_s": sum(tel.prefill_s[n_pre:]),
            "decode_s": sum(tel.step_s[n_dec:]),
            "n_prefill_calls": len(tel.prefill_s) - n_pre,
            "n_decode_calls": len(tel.step_s) - n_dec,
            "prefilled": prefilled, "decoded": decoded})


def run(ctx) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp

    from repro.models.lm import transformer as tf
    from repro.serve import EngineConfig, ServeEngine

    from drivers.cnn import same_layout
    from reference import phi4_decoder as ref

    cfg, mix = ctx.config, ctx.mix
    arch = program_config(cfg)
    params = make_weights(arch, cfg, ctx.seed)
    same_layout(params, jax.eval_shape(lambda k: tf.init(k, arch),
                                       jax.random.PRNGKey(0)))
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
            a = time.perf_counter()
            if tracing:
                with jax.profiler.TraceAnnotation("bench.step"):
                    engine.step()
            else:
                engine.step()
            track.after_step(a, time.perf_counter(), tel, n_pre, n_dec)
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
    if mix["arrival"] == "backlog":
        attempted = sum(1 for r in in_window if r["times"])
        failed = 0
    else:
        attempted = len(in_window)
        failed = sum(1 for r in in_window if not r["times"])
    rec: Dict[str, Any] = {
        "kind": "serve", "traffic": ctx.cell["traffic"],
        "seconds": ctx.seconds, "setup_s": setup_s, "late_s": late,
        "t0": t0, "t_end": t_end,
        "attempted": attempted, "failed": failed,
        "compiles_in_window": n_compiles, "memory_peak_bytes": mem,
        "requests": [{"due": r["due"], "prompt": r["prompt"],
                      "times": r["times"]} for r in reqs.values()],
        "steps": [s for s in track.steps if s["a"] < t_end],
        "config": cfg, "mix": mix, "n_slots": e["n_slots"],
    }
    if prof is not None:
        rec["trace"] = reduce_trace(events, track.steps[trace_from:trace_to])

    # correctness: served tokens of a sample of finished requests
    done = [r for r in reqs.values() if r["req"].done and r["req"].tokens]
    del engine, track
    gc.collect()
    pick = sample(done, generator.rng(ctx.seed, 9), mix["check_requests"])
    ref_fn = ref.logits_fn(cfg)
    ctl_fn = ref.logits_fn(cfg, quant=cfg["control"]) if ctx.control \
        else None
    b16_fn = ref.logits_fn(cfg, quant="bf16") if ctx.control else None
    worst = ctl_worst = noise = 0.0
    n_tok = 0
    for r in pick:
        req = r["req"]
        toks = np.zeros(mix["max_len"], np.int32)
        seq = np.concatenate([req.prompt, np.asarray(req.tokens, np.int32)])
        toks[:seq.size] = seq
        logits = np.asarray(ref_fn(params, jnp.asarray(toks)))
        worst = max(worst, widest_gap(logits, req.prompt.size, req.tokens))
        n_tok += len(req.tokens)
        if ctl_fn is not None:
            rows = slice(req.prompt.size - 1, seq.size - 1)
            first = np.argmax(np.asarray(
                ctl_fn(params, jnp.asarray(toks)))[rows], axis=1)
            ctl_worst = max(ctl_worst, widest_gap(logits, req.prompt.size,
                                                  first))
            b16 = np.asarray(b16_fn(params, jnp.asarray(toks)))[rows]
            noise = max(noise, float(np.max(np.abs(b16 - logits[rows]))))
    ctx.log(f"checked {n_tok} served tokens of {len(pick)} finished "
            f"requests against the reference")
    rec["checks"] = {"token_logit_gap": session.check(
        worst if pick else float("inf"), cfg["limits"]["token_logit_gap"])}
    if ctl_fn is not None:
        # a served token's gap is at most twice the largest logit error
        # of its step; bf16-rounded operands give that error's size
        rec["control"] = {"token_logit_gap": ctl_worst,
                          "bf16_logit_err": noise}
    return rec


def sample(done: List[Dict], gen: np.random.Generator, n: int
           ) -> List[Dict]:
    """The finished request with most served tokens and n - 1 others drawn
    from `gen`. Many requests, not many tokens: with random weights a
    sequence's greedy continuation settles, so each new context is what
    can bring a near tie between two tokens under the comparison."""
    if not done:
        return []
    longest = max(done, key=lambda r: len(r["req"].tokens))
    rest = [r for r in done if r is not longest]
    return [longest] + [rest[i] for i in
                        gen.permutation(len(rest))[:max(0, n - 1)]]


def widest_gap(logits: np.ndarray, prompt_len: int, served) -> float:
    """Largest amount by which a served token's reference logit lies below
    the reference's best logit at its position (0 where greedy decoding
    agrees exactly); inf if a logit is not finite."""
    rows = logits[prompt_len - 1: prompt_len - 1 + len(served)]
    if not np.all(np.isfinite(rows)):
        return float("inf")
    got = rows[np.arange(len(served)), np.asarray(served)]
    return float(np.max(rows.max(axis=1) - got))


def reduce_trace(events: Dict, steps: List[Dict]) -> Dict[str, Any]:
    """The traced engine steps (the benchmark's `bench.step` spans); time
    spent waiting for an arrival is outside them."""
    t = trace_reduce.window(events,
                            trace_reduce.spans(events["host"], "bench.step"))
    t["steps"] = steps
    return t
