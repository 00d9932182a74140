"""Prefill programs' device time as a share of device busy time, in %,
from the traced part of the window (jitted function ``_prefill_impl``)."""


def read(rec):
    tr = rec.get("trace")
    p = tr and tr["programs"].get("prefill")
    if not p or not tr["busy_s"]:
        return None
    return 100.0 * p["device_s"] / tr["busy_s"]
