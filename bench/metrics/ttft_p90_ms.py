"""90th percentile of the time to first token over every request due in
the window, each timed from when it was due; a request never served
counts as infinitely late."""
import math

import serving


def read(rec):
    if rec["kind"] != "serve":
        return None
    v = serving.percentile(serving.ttfts_s(rec), 90)
    return v * 1e3 if math.isfinite(v) else 1e30
