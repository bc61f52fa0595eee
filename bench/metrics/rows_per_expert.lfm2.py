"""Mean rows a held expert computes a decode call: the program's counter
of the rows routed to each held expert in each MoE layer, averaged over
the held experts, the MoE layers and the decode calls in the window."""
import numpy as np

import serving


def read(rec):
    if rec["kind"] != "serve":
        return None
    v = [np.mean(rows) for s in serving.window_steps(rec)
         for kind, rows in s.get("routed", []) if kind == "decode"]
    return float(np.mean(v)) if v else None
