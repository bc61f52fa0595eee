"""Median time of the engine's batched prefill and cache write (its
telemetry's `prefill_s`) in the offline-long cell."""
import numpy as np

import serving


def read(rec):
    if rec["kind"] != "serve" or rec["traffic"] != "offline-long":
        return None
    v = [s["prefill_s"] / s["n_prefill_calls"]
         for s in serving.window_steps(rec) if s["n_prefill_calls"]]
    return float(np.median(v)) * 1e3 if v else None
