"""Helpers every driver shares: seeds, the profiler session, device memory
and the form of a compared number."""
from __future__ import annotations

import math
import shutil
import tempfile
import time
from typing import Dict, List, Optional

import trace_reduce

TRACE_SECONDS = 8.0


def key(seed: int):
    """A JAX PRNG key for any whole-number seed (past 32 bits too)."""
    import jax

    k = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(k, (seed >> 31) & 0x7FFFFFFF)


def trace_span(seconds: float):
    """(start, stop) offsets into a window of `seconds` of the traced part:
    TRACE_SECONDS in its middle, or all of a shorter window."""
    t = min(TRACE_SECONDS, seconds)
    a = (seconds - t) / 2
    return a, a + t


class Profiler:
    """One profiler session, written to a temporary directory under
    TMPDIR that `stop` reads and removes."""

    def __init__(self):
        self.dir: Optional[str] = None
        self.t0 = self.t1 = 0.0

    def start(self) -> None:
        import jax

        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.t0 = time.perf_counter()

    def stop(self) -> Dict[str, List]:
        import jax

        self.t1 = time.perf_counter()
        jax.profiler.stop_trace()
        try:
            return trace_reduce.load(trace_reduce.xplane_file(self.dir))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest device, where the backend says."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))


def check(value: float, limit: float) -> Dict[str, float]:
    """A compared number beside its limit; anything not finite fails."""
    v = float(value)
    return {"value": v if math.isfinite(v) else 1e30, "limit": float(limit)}
