"""Images of every batch sent in the window, over the window, which
closes when the last of them has completed."""


def read(rec):
    if rec["kind"] != "cnn":
        return None
    return rec["images"] / rec["window_s"]
