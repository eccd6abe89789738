"""From a profiler trace (``.xplane.pb``) to intervals, and from
intervals to the numbers the per-layer metrics report.

Device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
event per executed operation, named by its HLO text (``%fusion.3 = ...``,
a loop's body ops nested inside the loop's event), and ``XLA Modules``
one per program run (``jit_<function>(<id>)``).
Host spans are the benchmark's own ``jax.profiler.TraceAnnotation``s,
named ``bench.<what>``, on the same clock.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

@dataclasses.dataclass
class Trace:
    """Events as (name, start_ns, end_ns), per device and for the host."""
    ops: dict            # device name -> [(name, t0, t1)] of XLA Ops
    modules: dict        # device name -> [(name, t0, t1)] of XLA Modules
    host: list           # [(name, t0, t1)] of bench.* spans

    def window(self) -> tuple:
        """The ``bench.window`` span, or the extent of all device ops."""
        spans = [(a, b) for n, a, b in self.host if n == "bench.window"]
        if spans:
            return spans[0]
        evs = [e for v in self.ops.values() for e in v]
        return min(e[1] for e in evs), max(e[2] for e in evs)


def load(trace_dir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    ops, modules, host = {}, {}, []
    for plane in pd.planes:
        dev = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        for line in plane.lines:
            evs = [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                   for e in line.events]
            if dev and line.name == "XLA Ops":
                ops[plane.name] = evs
            elif dev and line.name == "XLA Modules":
                modules[plane.name] = evs
            elif not dev:
                host += [e for e in evs if e[0].startswith("bench.")]
    return Trace(ops=ops, modules=modules, host=host)


def op_name(event_name: str) -> str:
    """The HLO instruction's name (``fusion.3``) from an op event's
    text, or the text itself."""
    m = re.match(r"%?([\w.-]+)\s*=", event_name)
    return m.group(1) if m else event_name


def self_times(events) -> list:
    """(name, self ns) per event: its time less that of the events
    nested directly inside it on the same line."""
    out = []
    stack = []          # [name, t0, t1, child ns]
    for n, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][2] <= a:
            top = stack.pop()
            out.append((top[0], top[2] - top[1] - top[3]))
        if stack:
            stack[-1][3] += min(b, stack[-1][2]) - a
        stack.append([n, a, b, 0])
    out += [(n, b - a - c) for n, a, b, c in stack]
    return out


def union(intervals) -> list:
    """Merge (t0, t1) intervals into disjoint sorted ones."""
    out = []
    for a, b in sorted((a, b) for a, b in intervals if b > a):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [tuple(x) for x in out]


def clip(intervals, lo, hi) -> list:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def length(intervals) -> int:
    return sum(b - a for a, b in union(intervals))


def subtract(intervals, minus) -> list:
    """The parts of ``intervals`` that no interval of ``minus`` covers."""
    minus = union(minus)
    out = []
    for a, b in union(intervals):
        cur = a
        for c, d in minus:
            if d <= cur or c >= b:
                continue
            if c > cur:
                out.append((cur, c))
            cur = max(cur, d)
        if cur < b:
            out.append((cur, b))
    return out


def busy_ns(ops, lo, hi) -> int:
    """Time in [lo, hi) in which some operation ran."""
    return length(clip([(a, b) for _, a, b in ops], lo, hi))


def idle_share(trace: Trace) -> float:
    """1 - busy / window, averaged over the devices."""
    lo, hi = trace.window()
    shares = [1 - busy_ns(ops, lo, hi) / (hi - lo)
              for ops in trace.ops.values()]
    return sum(shares) / len(shares)


def idle_gaps(trace: Trace, device: str, top: int = 10) -> list:
    """The longest gaps between device ops in the window, each named by
    the host span that covers most of it (``host`` when none does)."""
    lo, hi = trace.window()
    busy = union(clip([(a, b) for _, a, b in trace.ops[device]], lo, hi))
    gaps = sorted(subtract([(lo, hi)], busy), key=lambda g: g[0] - g[1])
    named = []
    for a, b in gaps[:top]:
        best, cover = "host", 0
        for n, c, d in trace.host:
            if n == "bench.window":
                continue
            ov = min(b, d) - max(a, c)
            if ov > cover:
                best, cover = n, ov
        named.append((best, (b - a) * 1e-9))
    return named


def top_ops(trace: Trace, device: str, top: int = 10) -> list:
    """The device operations that took most self time in the window, by
    instruction name with the instance number dropped."""
    lo, hi = trace.window()
    tot = {}
    inside = clip_events(trace.ops[device], lo, hi)
    for n, t in self_times(inside):
        key = re.sub(r"\.\d+$", "", op_name(n))
        tot[key] = tot.get(key, 0) + t
    return sorted(([k, v * 1e-9] for k, v in tot.items()),
                  key=lambda x: -x[1])[:top]


def clip_events(events, lo, hi) -> list:
    return [(n, max(a, lo), min(b, hi)) for n, a, b in events
            if min(b, hi) > max(a, lo)]
