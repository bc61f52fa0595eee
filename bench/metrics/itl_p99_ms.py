"""99th percentile of the gap between consecutive output tokens of a
request, over every gap that ends inside the window. A token is stamped
with the end of the engine step that emitted it."""
import serving


def read(rec):
    if rec["kind"] != "serve":
        return None
    gaps = serving.token_gaps_s(rec)
    return serving.percentile(gaps, 99) * 1e3 if gaps else None
