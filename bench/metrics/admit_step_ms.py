"""Median, over the engine ``step()`` calls that ran a prefill, of the
step's host-clock time divided by its number of prefills."""
import numpy as np


def value(run):
    t = [(s["t1"] - s["t0"]) / s["prefills"] for s in run.records["steps"]
         if s["prefills"] > 0]
    return float(np.median(t)) * 1e3 if t else None
