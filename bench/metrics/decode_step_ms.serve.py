"""Median time of the engine's decode call (its telemetry's `step_s`:
dispatch to the fetched next tokens)."""
import numpy as np

import serving


def read(rec):
    if rec["kind"] != "serve":
        return None
    v = [s["decode_s"] / s["n_decode_calls"]
         for s in serving.window_steps(rec) if s["n_decode_calls"]]
    return float(np.median(v)) * 1e3 if v else None
