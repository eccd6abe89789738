"""Median host-clock time of an engine ``step()`` that admitted nothing
(the engine's ``prefills`` counter unchanged) and decoded."""
import numpy as np


def value(run):
    t = [s["t1"] - s["t0"] for s in run.records["steps"]
         if s["prefills"] == 0 and s["decoded"]]
    return float(np.median(t)) * 1e3 if t else None
