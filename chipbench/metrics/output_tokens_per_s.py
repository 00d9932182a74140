"""Output tokens emitted inside the window (the first token of a request,
from its prefill, and every decoded token), over the window's length."""


def read(rec):
    return rec["tokens_in_window"] / rec["window_s"]
