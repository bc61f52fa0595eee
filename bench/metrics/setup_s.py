"""Set-up time: process start to the first timed batch or request."""


def read(rec):
    return rec["setup_s"]
