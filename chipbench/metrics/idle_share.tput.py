"""Share of the traced part of the window in which no program ran on the
device, in %: 1 - (union of device program intervals) / window."""


def read(rec):
    tr = rec.get("trace")
    if not tr or not tr["window_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
