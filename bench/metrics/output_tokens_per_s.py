"""Every output token emitted inside the window, finished requests or not,
over the window."""
import serving


def read(rec):
    if rec["kind"] != "serve":
        return None
    return serving.tokens_in_window(rec) / rec["seconds"]
