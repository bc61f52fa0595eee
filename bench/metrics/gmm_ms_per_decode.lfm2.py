"""Device time of the grouped CADC expert kernel (`cadc_gmm_pallas`) a
decode call: the median, over the traced engine steps that ran a decode
call and no prefill, of the kernel's event time inside the step's span
over its decode calls."""
import numpy as np

from drivers.serve_lfm2 import kernel_ns_per_step

KERNEL = "cadc_gmm_pallas"


def read(rec):
    t = rec.get("trace")
    if rec["kind"] != "serve" or not t or not t.get("step_spans"):
        return None
    v = [ns / s["n_decode_calls"]
         for s, ns in zip(t["steps"], kernel_ns_per_step(t, KERNEL))
         if s["n_decode_calls"] and not s["n_prefill_calls"] and ns > 0]
    return float(np.median(v)) * 1e-6 if v else None
