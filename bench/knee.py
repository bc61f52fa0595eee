"""Sweep of offered rates for an open-loop serving cell, to find its knee.

    python bench/knee.py --workload <cell> --rates 1,2,3,4 --seconds 30

Runs the cell once per rate in this one process, with the mix's
`rate_per_s` replaced, and prints for each rate what was offered and
served: output tokens/s, the tails of time to first token, and the backlog
when the window closed (requests due but not yet given a slot). The knee
is the highest rate whose backlog stays small and whose time to first
token does not climb with the window; the cell's mix then fixes a rate
below it as a number. Needs the chip, as `run.py` does.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time

import run
import serving


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated req/s")
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    bench, cell, config, mix = run.load_cell(args.workload)
    for rate in (float(r) for r in args.rates.split(",")):
        m = dict(mix, rate_per_s=rate)
        m.pop("drain_s", None)
        out = run.run_loaded(bench, cell, config, m, args.seed, args.seconds,
                             False, t_start=time.perf_counter())
        rec = out["record"]
        due = [r for r in rec["requests"] if r["due"] < rec["t_end"]]
        half = rec["t0"] + args.seconds / 2
        ttft = serving.ttfts_s(rec)
        first = [t for r, t in zip(due, ttft) if r["due"] < half]
        second = [t for r, t in zip(due, ttft) if r["due"] >= half]
        pct = (lambda v, q: serving.percentile(v, q) * 1e3
               if v and math.isfinite(serving.percentile(v, q)) else None)
        print(json.dumps({
            "rate_per_s": rate, "due": len(due),
            "not_served": sum(1 for r in due if not r["times"]),
            "output_tokens_per_s": serving.tokens_in_window(rec)
            / args.seconds,
            "ttft_p50_ms": pct(ttft, 50), "ttft_p90_ms": pct(ttft, 90),
            "ttft_p90_ms_first_half": pct(first, 90),
            "ttft_p90_ms_second_half": pct(second, 90),
            "late_s": rec["late_s"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
