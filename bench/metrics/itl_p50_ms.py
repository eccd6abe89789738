"""Median of every gap between successive tokens of every request, both
tokens inside the window (tokens that one step delivers together are
gaps of 0): the pace at which a reader sees an answer stream."""
import numpy as np

from bench.traffic import percentile


def value(run):
    w = run.records["window_s"]
    gaps = []
    for r in run.records["requests"].values():
        t = [x for x in r["t"] if x <= w]
        gaps += list(np.diff(t))
    return percentile(gaps, 50) * 1e3 if gaps else None
