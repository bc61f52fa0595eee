"""Continuous-batching serving driver — thin CLI over repro.serve.

    PYTHONPATH=src python -m repro.launch.serve --arch gemma3_1b --smoke \
        --slots 4 --requests 12 --rate 0.5 --prompt-len 16 --gen 16

Requests arrive as a Poisson-style synthetic stream (more requests than
slots => the engine exercises admission queueing, finished-sequence
eviction and slot/block reuse). Prefill is BATCHED by default (one
full-sequence forward per admission wave, per-slot prompt lengths);
--prefill-via-decode restores the legacy token-at-a-time path, which
builds the caches through the decode step itself and thereby checks the
cache-consistency invariant end to end. --backend picks the paged
(block-table KV pools) or dense (per-slot rings) cache layout — the two
are bit-identical on the decode path (tests/test_serve_engine.py).
--spec-tokens K turns decode iterations into draft/verify steps (K drafts
per slot scored in one multi-token paged append; --draft picks the
proposer) without changing the committed token streams — greedy-exact
speculative decoding (tests/test_speculative.py).

Multi-host note: the engine runs single-process today; the sharding rules
for the paged pools exist (sharding.paged_cache_specs — kv-heads over
'model') but are not yet applied on the serving path. Wiring them in is
the 'multi-host engine' ROADMAP item.
"""
from __future__ import annotations

import argparse
import json

import jax
import numpy as np

from repro.configs import get_config, smoke_config
from repro.launch import compile_cache
from repro.launch.train import make_local_mesh
from repro.models.lm import transformer as tf
from repro.serve import EngineConfig, ServeEngine, poisson_workload


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--cadc", action="store_true")
    ap.add_argument("--slots", "--batch", type=int, default=None,
                    dest="slots", help="concurrent cache slots (default: "
                    "cfg.serve_slots; --batch kept as the legacy alias)")
    ap.add_argument("--requests", type=int, default=None,
                    help="total synthetic requests (default 2x slots — "
                    "forces slot reuse)")
    ap.add_argument("--rate", type=float, default=0.5,
                    help="Poisson arrivals per decode step")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=None)
    ap.add_argument("--block-size", type=int, default=None)
    ap.add_argument("--backend", choices=["paged", "dense"], default="paged")
    ap.add_argument("--prefill-via-decode", action="store_true",
                    help="token-at-a-time prefill through the decode step "
                    "(cache-consistency invariant check)")
    ap.add_argument("--telemetry-every", type=int, default=None,
                    help="sample per-layer CADC psum sparsity every N decode "
                    "steps (each sample re-runs one step with xla kernels; "
                    "default: cfg.serve_telemetry_every, 0 = off)")
    ap.add_argument("--attn-impl", default=None,
                    choices=["auto", "pallas", "interpret", "xla"],
                    help="paged-attention backend (default "
                    "cfg.paged_attn_impl: fused flash-decoding kernel on "
                    "TPU, gather fallback elsewhere)")
    ap.add_argument("--spec-tokens", type=int, default=0,
                    help="speculative decoding: K draft tokens verified "
                    "per slot per step in one multi-token paged append "
                    "(0 = off; committed streams stay bit-identical to "
                    "plain greedy decode)")
    ap.add_argument("--draft", choices=["ngram", "model"], default="ngram",
                    help="draft proposer for --spec-tokens: prompt-lookup "
                    "n-gram (model-free) or a shrunk-config draft model")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    compile_cache.enable()

    cfg = (smoke_config if args.smoke else get_config)(args.arch)
    if args.cadc:
        cfg = cfg.with_overrides(linear_impl="cadc")
    if args.attn_impl is not None:
        cfg = cfg.with_overrides(paged_attn_impl=args.attn_impl)
    if not cfg.supports_decode():
        raise SystemExit(f"{cfg.name} is encoder-only: no decode step")

    slots = args.slots or cfg.serve_slots
    block = args.block_size or cfg.serve_block_size
    max_len = args.max_len or (args.prompt_len + args.gen)
    max_len = -(-max_len // block) * block  # round up to block granularity
    n_requests = args.requests or 2 * slots

    mesh = make_local_mesh()
    params = tf.init(jax.random.PRNGKey(0), cfg)
    engine = ServeEngine(cfg, params, EngineConfig(
        n_slots=slots,
        max_len=max_len,
        block_size=block,
        backend=args.backend,
        prefill_mode="decode" if args.prefill_via_decode else "batched",
        telemetry_every=args.telemetry_every,
        spec_tokens=args.spec_tokens,
        spec_draft=args.draft,
    ))
    workload = poisson_workload(
        n_requests=n_requests, rate=args.rate, vocab_size=cfg.vocab_size,
        prompt_len=(max(1, args.prompt_len // 2), args.prompt_len),
        max_new=(max(1, args.gen // 2), args.gen), seed=args.seed)

    with mesh:
        summary = engine.run(workload)

    print(f"arch={cfg.name} cadc={args.cadc} backend={args.backend} "
          f"slots={slots} requests={n_requests} "
          f"prefill={'decode' if args.prefill_via_decode else 'batched'}:")
    print(f"  {summary['tokens_per_s']:.1f} tok/s over "
          f"{summary['decode_tokens']} decode tokens "
          f"({summary['requests_finished']} requests)")
    print(f"  step ms p50/p99 = {summary['step_ms_p50']:.1f}/"
          f"{summary['step_ms_p99']:.1f}  TTFT ms p50/p99 = "
          f"{summary['ttft_ms_p50']:.1f}/{summary['ttft_ms_p99']:.1f}")
    if "speculative" in summary:
        sp = summary["speculative"]
        print(f"  speculative (K={args.spec_tokens}, draft={args.draft}): "
              f"accept rate {sp['accept_rate']:.2f}, "
              f"{sp['tokens_per_step']:.2f} tokens/slot/step "
              f"({sp['accepted']}/{sp['drafted']} drafts over "
              f"{sp['steps']} steps)")
    if "blocks" in summary:
        print(f"  blocks: {json.dumps(summary['blocks'])}")
    if "psum_sparsity" in summary:
        gates = [v["gate_off"] for v in summary["psum_sparsity"].values()]
        print(f"  psum gate-off fraction: mean={float(np.mean(gates)):.3f} "
              f"over {len(gates)} tapped linears")
    rid0 = min(engine.results)
    print(f"sample continuation (req {rid0}): "
          f"{engine.results[rid0].tokens[:12]}")
    return summary


if __name__ == "__main__":
    main()
