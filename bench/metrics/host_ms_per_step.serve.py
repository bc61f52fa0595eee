"""Median host time of an engine step: the benchmark's span around
`engine.step()` less the engine telemetry's device-synchronised prefill
and decode times of that step."""
import numpy as np

import serving


def read(rec):
    if rec["kind"] != "serve":
        return None
    v = [s["b"] - s["a"] - s["prefill_s"] - s["decode_s"]
         for s in serving.window_steps(rec)]
    return float(np.median(v)) * 1e3 if v else None
