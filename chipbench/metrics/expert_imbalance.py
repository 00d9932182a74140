"""Routing imbalance over the window, from the program's own counter of
routed tokens per (layer, expert) (``ExpertTelemetry.demand``): per
layer the busiest expert's tokens over the mean, averaged over layers.
1 is perfectly even."""
import numpy as np


def read(rec):
    d = rec.get("demand")
    if d is None:
        return None
    d = np.asarray(d, float)
    mean = d.mean(1)
    live = mean > 0
    if not live.any():
        return None
    return float((d.max(1)[live] / mean[live]).mean())
