"""Device time of the decode-step program per decode step, in ms, from
the traced part of the window (jitted function ``_decode_impl``)."""


def read(rec):
    tr = rec.get("trace")
    p = tr and tr["programs"].get("decode")
    if not p or not p["count"]:
        return None
    return 1e3 * p["device_s"] / p["count"]
