"""Seconds from process start to the window's first due request: loading,
weights, engine, and the warm-up of every program shape (compilation
included where the cache does not hold it)."""


def read(rec):
    return rec["setup_s"]
