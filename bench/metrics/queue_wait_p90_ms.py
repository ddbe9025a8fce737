"""Admission: due time to the engine's grant of cache slots, 90th
percentile over the requests due and granted in the traced stretch."""
import numpy as np


def read(run):
    lo, hi = run.window
    w = [r.granted - r.due for r in run.loop.records
         if r.granted is not None and lo <= r.due and r.granted < hi]
    return float(np.percentile(w, 90)) * 1e3 if w else None
