"""The decode program's share of the chip's peak: the least time the
chip could take for the window's decode steps (the larger of their
required flops over the matmul peak and their required bytes over the
HBM peak, from shapes and live lengths; ``bench.counts.decode_step``)
over the device time of the decode program's runs in the trace."""
import re

import numpy as np

from bench import counts

DECODE = re.compile(r"step_impl")


def value(run):
    tr, peaks = run.trace, run.ctx.peaks
    if tr is None or peaks is None or not tr.modules:
        return None
    lo, hi = tr.window()
    dev = sorted(tr.modules)[0]
    busy = sum(b - a for n, a, b in tr.modules[dev]
               if DECODE.search(n) and a >= lo and b <= hi) * 1e-9
    s = run.out["sizes"]
    wb = np.dtype(run.ctx.config["dtype"]).itemsize
    cb = np.dtype(run.ctx.config["serve"]["cache_dtype"]).itemsize
    least = 0.0
    for st in run.records["steps"]:
        if st["decoded"] and st["lengths"]:
            f, b = counts.decode_step(s, st["lengths"], wb, cb)
            least += max(f / peaks["matmul_flops_per_s"],
                         b / peaks["hbm_bytes_per_s"])
    return 100.0 * least / busy if busy > 0 else None
