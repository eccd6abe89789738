"""The one traffic generator: a mix file's parameters and a seed in,
requests out.

Every seed gets the same set of sizes and arrival gaps, in another
order: lengths are the quantiles ``(i + 0.5) / n`` of the mix's
lognormal, clipped and rounded up, and the gaps between arrivals the
same quantiles of an exponential at the mix's rate.  So a run's work is
fixed by the mix and the window, and the seed changes only its order
and the token ids.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass(frozen=True)
class Req:
    id: str
    due_s: float            # seconds after the window opens
    prompt: np.ndarray      # int32 token ids
    max_new_tokens: int


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths of a lognormal spec, in quantile order."""
    z = np.array([NormalDist().inv_cdf(q) for q in _quantiles(n)])
    x = spec["lognormal_median"] * np.exp(spec["lognormal_sigma"] * z)
    x = np.clip(np.ceil(x), spec["min"], spec["max"])
    r = spec.get("round_up_to", 1)
    return (np.ceil(x / r) * r).astype(np.int64)


def arrivals(rate: float, seconds: float, rng) -> np.ndarray:
    """Due times of a Poisson stream at ``rate`` over ``seconds``: the
    exponential's quantile gaps, shuffled, summed; the first is due
    when the window opens."""
    n = max(1, int(round(rate * seconds)))
    gaps = rng.permutation(-np.log1p(-_quantiles(n)) / rate)
    return np.cumsum(gaps) - gaps[0]


def requests(mix: dict, seed: int, seconds: float, vocab: int) -> list:
    """The open-loop requests due in ``[0, seconds)``."""
    rng = np.random.default_rng(int(seed))
    due = arrivals(mix["rate_per_s"], seconds, rng)
    due = due[due < seconds]
    n = len(due)
    prompt = rng.permutation(lengths(mix["prompt_tokens"], n))
    out = rng.permutation(lengths(mix["output_tokens"], n))
    return [Req(id=f"r{i}", due_s=float(due[i]),
                prompt=rng.integers(0, vocab, int(prompt[i]),
                                    dtype=np.int32),
                max_new_tokens=int(out[i])) for i in range(n)]


def percentile(values, q: float) -> float:
    """The ``q``-th percentile by linear interpolation between order
    statistics (numpy's default); nan for no values."""
    if len(values) == 0:
        return math.nan
    return float(np.percentile(np.asarray(values, np.float64), q))
