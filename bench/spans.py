"""The serving engine's own spans (``serve.*``) in a traced window, with
their stats, and the chip's busy and idle time inside them.

The engine opens each span as a ``jax.profiler.TraceAnnotation``
(``repro.obs.trace.span``), so they lie on the host plane of the
``.xplane.pb``, on the clock of the device ops.  Their names and
nesting:

    serve.step
        serve.admit (req, tokens)            one per admission
            serve.prefill                    runs jit_prefill_impl
            serve.kv.extract / serve.kv.insert
            serve.sample                     the first token
        serve.kv.extract                     an eviction
        serve.decode                         runs jit_step_impl
        serve.sample                         the decode step's tokens

A program without these spans gives none, and every reader then
returns ``None``.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

import numpy as np

from bench import trace_reduce as tr

CODEC = ("serve.kv.extract", "serve.kv.insert")
PREFILL = re.compile(r"jit_prefill_impl")


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    t0: int              # ns, the device trace's clock
    t1: int
    stats: dict

    @property
    def ns(self) -> int:
        return self.t1 - self.t0


_LOADED: dict = {}       # .xplane.pb path -> [Span], in start order


def load(path: str) -> list:
    """Every ``serve.*`` host event of one ``.xplane.pb``."""
    if path not in _LOADED:
        from jax.profiler import ProfileData
        out = []
        for plane in ProfileData.from_file(path).planes:
            if re.fullmatch(r"/device:TPU:\d+", plane.name):
                continue
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("serve."):
                        t0 = int(e.start_ns)
                        out.append(Span(e.name, t0,
                                        t0 + int(e.duration_ns),
                                        dict(e.stats)))
        _LOADED[path] = sorted(out, key=lambda s: (s.t0, -s.t1))
    return _LOADED[path]


def window_spans(run) -> list:
    """The engine's spans wholly inside the traced window of ``run``
    (empty without a trace)."""
    if run.trace is None or not run.ctx.trace_dir:
        return []
    files = sorted(glob.glob(os.path.join(run.ctx.trace_dir, "**",
                                          "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not files:
        return []
    lo, hi = run.trace.window()
    return [s for s in load(files[-1]) if lo <= s.t0 and s.t1 <= hi]


def chip_busy(run):
    """Disjoint sorted intervals in which some op ran on the first TPU
    device, or ``None`` when the trace has no TPU plane."""
    if run.trace is None or not run.trace.ops:
        return None
    ops = run.trace.ops[sorted(run.trace.ops)[0]]
    return tr.union([(a, b) for _, a, b in ops])


def prefill_runs(run):
    """(t0, t1) of each run of the prefill program on the first TPU
    device, or ``None`` when the trace has no TPU plane."""
    if run.trace is None or not run.trace.modules:
        return None
    mods = run.trace.modules[sorted(run.trace.modules)[0]]
    return [(a, b) for n, a, b in mods if PREFILL.search(n)]


def idle_ns(span: Span, busy: list) -> int:
    """Time inside ``span`` in which no interval of ``busy`` (disjoint,
    sorted) runs."""
    i = max(bisect.bisect_right(busy, (span.t0,)) - 1, 0)
    j = bisect.bisect_left(busy, (span.t1,))
    return span.ns - tr.length(tr.clip(busy[i:j], span.t0, span.t1))


def holds(outer: Span, s: Span) -> bool:
    return s is not outer and outer.t0 <= s.t0 and s.t1 <= outer.t1


def inside(outer: Span, spans: list, names) -> list:
    """The spans named in ``names`` that lie within ``outer``."""
    return [s for s in spans if s.name in names and holds(outer, s)]


def median_ms(ns: list):
    return float(np.median(ns)) * 1e-6 if ns else None


def named(spans: list, name: str) -> list:
    return [s for s in spans if s.name == name]


def prefill_device_ns(spans: list, runs: list) -> list:
    """Per ``serve.prefill``, the device time of the prefill program's
    runs inside it."""
    return [tr.length(tr.clip(runs, s.t0, s.t1))
            for s in named(spans, "serve.prefill")]


def admit_codec_ns(spans: list) -> list:
    """Per admission, its extract and insert time (0 without either)."""
    return [sum(c.ns for c in inside(a, spans, CODEC))
            for a in named(spans, "serve.admit")]


def admit_idle_ns(spans: list, busy: list) -> list:
    return [idle_ns(a, busy) for a in named(spans, "serve.admit")]


def decode_sample_ns(spans: list) -> list:
    """Durations of the ``serve.sample`` spans outside any admission."""
    admits = named(spans, "serve.admit")
    return [s.ns for s in named(spans, "serve.sample")
            if not any(holds(a, s) for a in admits)]


def decode_step_idle_ns(spans: list, busy: list) -> list:
    """Chip-idle time inside each ``serve.step`` that decoded and
    admitted nothing."""
    out = []
    for st in named(spans, "serve.step"):
        kids = {s.name for s in inside(st, spans,
                                       ("serve.decode", "serve.admit"))}
        if kids == {"serve.decode"}:
            out.append(idle_ns(st, busy))
    return out
