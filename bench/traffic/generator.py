"""The one traffic generator: reads a mix file (`bench/traffic/<mix>.json`)
and makes that mix's inputs from `--seed`.

Two kinds of mix:

* ``"batches"``: closed loop. A pool of distinct input batches, made on the
  device from the seed; the driver sends them back to back.
* ``"requests"``: open loop in wall time. Arrival times (Poisson at
  ``rate_per_s``, or every request due at t = 0), prompt and output
  lengths from clipped lognormals, prompt token ids.

Every seed gets the same schedule: the lengths and the inter-arrival
gaps, in their order, are drawn once from the mix's own ``base_seed``;
the run's seed draws only the token ids. A Poisson window of T seconds
holds exactly round(rate * T) arrivals, as a Poisson process given its
count does. Runs on different seeds then do the same work in the same
order, and their spread is the system's, not the sample's: at a load
near the knee the order of arrivals alone moves the tail of time to
first token several-fold.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Dict, List

import numpy as np

HERE = Path(__file__).resolve().parent


def load(name: str) -> Dict:
    """The mix file of traffic `name`."""
    path = HERE / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} at {path}")
    return json.loads(path.read_text())


def rng(seed: int, *salt: int) -> np.random.Generator:
    """A numpy generator for any whole-number seed below 2**64 in size."""
    return np.random.default_rng([seed & 0xFFFFFFFF,
                                  (seed >> 32) & 0xFFFFFFFF, *salt])


def _lengths(spec: Dict, n: int, gen: np.random.Generator) -> np.ndarray:
    x = spec["median"] * np.exp(spec["sigma"] * gen.standard_normal(n))
    return np.clip(np.round(x), spec["min"], spec["max"]).astype(np.int64)


@dataclasses.dataclass
class Schedule:
    """An open-loop request schedule: request i is due at `arrival_s[i]`
    seconds after the window opens, with prompt `prompts[i]` and
    `max_new[i]` output tokens."""

    arrival_s: np.ndarray
    prompts: List[np.ndarray]
    max_new: np.ndarray

    def __len__(self) -> int:
        return len(self.max_new)


def n_requests(mix: Dict, seconds: float) -> int:
    """How many requests a window of `seconds` is given: the mix's fixed
    backlog, or the Poisson arrivals that the rate puts in the window."""
    if mix["arrival"] == "backlog":
        return int(mix["requests"])
    return max(1, int(round(mix["rate_per_s"] * seconds)))


def schedule(mix: Dict, seed: int, seconds: float, vocab_size: int
             ) -> Schedule:
    n = n_requests(mix, seconds)
    base = rng(mix["base_seed"], n)
    prompt_len = _lengths(mix["prompt"], n, base)
    max_new = _lengths(mix["output"], n, base)
    if mix["arrival"] == "backlog":
        arrival = np.zeros(n)
    elif mix["arrival"] == "poisson":
        # n + 1 exponential gaps scaled to sum to the window: their first
        # n partial sums are n uniform order statistics in [0, seconds)
        gaps = base.exponential(1.0 / mix["rate_per_s"], n + 1)
        arrival = np.cumsum(gaps)[:n] * (seconds / gaps.sum())
    else:
        raise ValueError(f"unknown arrival process {mix['arrival']!r}")
    tok = rng(seed, 3)
    prompts = [tok.integers(0, vocab_size, int(p), dtype=np.int32)
               for p in prompt_len]
    return Schedule(arrival, prompts, max_new)


def prefill_lengths(mix: Dict) -> List[int]:
    """One prompt length per power-of-two bucket the mix can produce, and
    the longest prompt: what a warm-up has to send to see every prefill
    shape the window will."""
    lo, hi = mix["prompt"]["min"], mix["prompt"]["max"]
    out, b = [lo], 8
    while b < hi:
        if b * 2 >= lo:
            out.append(max(lo, b + 1))
        b *= 2
    out.append(hi)
    return sorted(set(out))
