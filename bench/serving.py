"""Arithmetic the serving metrics share, over a run record's requests
(when each was due, when each of its tokens came out) and steps."""
from __future__ import annotations

import math
from typing import Dict, List


def percentile(values: List[float], q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least q% of
    the values at or below it."""
    v = sorted(values)
    return v[max(0, math.ceil(q / 100 * len(v)) - 1)]


def tokens_in_window(rec: Dict) -> int:
    return sum(sum(1 for t in r["times"] if t <= rec["t_end"])
               for r in rec["requests"])


def ttfts_s(rec: Dict) -> List[float]:
    """Time to first token of every request due in the window, from when
    it was due; inf for one that got none."""
    return [(r["times"][0] - r["due"]) if r["times"] else math.inf
            for r in rec["requests"] if r["due"] < rec["t_end"]]


def window_steps(rec: Dict) -> List[Dict]:
    return [s for s in rec["steps"] if s["b"] <= rec["t_end"]]
