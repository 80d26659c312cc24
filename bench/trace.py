"""Reduce a JAX profiler trace (``.xplane.pb``) to what the metrics read.

One clock for everything: the profiler puts host and device events on the
same nanosecond timeline, so a device gap can be named by the benchmark
span that was open on the host while it lasted.

* device op events: the ``XLA Ops`` line of every ``/device:*`` plane,
  each ``(name, start_ns, duration_ns, stats)``;
* program events: the ``XLA Modules`` line (one event per executed program);
* host spans: events whose name starts with ``bench.`` on any host line
  (``jax.profiler.TraceAnnotation`` written by the benchmark).

Busy time is the union of the op intervals of a device, averaged over the
devices that ran anything.  Idle gaps are the holes in that union inside
the traced window.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from typing import Callable, Dict, List, Optional, Tuple

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Event:
    name: str
    start: float            # ns
    dur: float              # ns
    stats: Dict[str, str]
    device: int = 0

    @property
    def end(self) -> float:
        return self.start + self.dur


def find_xplane(directory: str) -> str:
    """The newest ``.xplane.pb`` under a profiler output directory."""
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return max(paths, key=os.path.getmtime)


def _stats(ev) -> Dict[str, str]:
    return {str(k): str(v) for k, v in ev.stats}


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


class Trace:
    """The reduced trace; every query takes an optional ``(t0, t1)`` window
    and clips intervals to it."""

    def __init__(self, ops: List[Event], modules: List[Event],
                 spans: List[Event]):
        self.ops = ops
        self.modules = modules
        self.spans = spans

    # ------------------------------------------------------------------ #
    @classmethod
    def load(cls, path: str) -> "Trace":
        from jax.profiler import ProfileData
        if os.path.isdir(path):
            path = find_xplane(path)
        data = ProfileData.from_file(path)
        ops, modules, spans = [], [], []
        for plane in data.planes:
            if plane.name.startswith("/device:"):
                try:
                    dev = int(plane.name.rsplit(":", 1)[1])
                except ValueError:
                    dev = 0
                for line in plane.lines:
                    if line.name not in (OPS_LINE, MODULES_LINE):
                        continue
                    dst = ops if line.name == OPS_LINE else modules
                    for ev in line.events:
                        dst.append(Event(ev.name, float(ev.start_ns),
                                         float(ev.duration_ns), _stats(ev),
                                         dev))
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name.startswith(SPAN_PREFIX):
                            spans.append(Event(ev.name, float(ev.start_ns),
                                               float(ev.duration_ns), {}))
        return cls(ops, modules, spans)

    # ------------------------------------------------------------------ #
    def window(self) -> Tuple[float, float]:
        """The ``bench.window`` span, else the extent of all device ops."""
        win = [s for s in self.spans if s.name == WINDOW_SPAN]
        if win:
            w = max(win, key=lambda s: s.dur)
            return w.start, w.end
        if not self.ops:
            raise ValueError("trace holds no device op and no window span")
        return (min(e.start for e in self.ops),
                max(e.end for e in self.ops))

    def window_s(self) -> float:
        t0, t1 = self.window()
        return (t1 - t0) / 1e9

    def _clip(self, ev: Event, t0, t1) -> Tuple[float, float]:
        return max(ev.start, t0), min(ev.end, t1)

    def devices(self) -> List[int]:
        return sorted({e.device for e in self.ops})

    def busy_intervals(self, device: int, t0=None, t1=None):
        if t0 is None:
            t0, t1 = self.window()
        iv = []
        for e in self.ops:
            if e.device != device:
                continue
            s, en = self._clip(e, t0, t1)
            if en > s:
                iv.append((s, en))
        return _union(iv)

    def busy_s(self, t0=None, t1=None) -> float:
        """Seconds in which an op ran, averaged over the devices used."""
        devs = self.devices()
        if not devs:
            return 0.0
        tot = sum(sum(e - s for s, e in self.busy_intervals(d, t0, t1))
                  for d in devs)
        return tot / len(devs) / 1e9

    def op_time_s(self, match: Callable[[Event], bool], t0=None,
                  t1=None) -> float:
        """Summed device seconds of the op events ``match`` accepts,
        averaged over the devices used."""
        if t0 is None:
            t0, t1 = self.window()
        tot = 0.0
        for e in self.ops:
            if match(e):
                s, en = self._clip(e, t0, t1)
                tot += max(0.0, en - s)
        return tot / max(len(self.devices()), 1) / 1e9

    def module_time_s(self, match: Callable[[Event], bool], t0=None,
                      t1=None) -> float:
        if t0 is None:
            t0, t1 = self.window()
        tot = 0.0
        for e in self.modules:
            if match(e):
                s, en = self._clip(e, t0, t1)
                tot += max(0.0, en - s)
        return tot / max(len(self.devices()), 1) / 1e9

    def ops_in_window(self, match: Callable[[Event], bool]) -> List[Event]:
        t0, t1 = self.window()
        return [e for e in self.ops if match(e) and e.start >= t0
                and e.end <= t1]

    def self_times(self, device: int) -> List[Tuple[Event, float]]:
        """Each op of ``device`` with its own time: an op that encloses
        others (a while loop and its body) keeps only what they leave."""
        evs = sorted((e for e in self.ops if e.device == device),
                     key=lambda e: (e.start, -e.dur))
        own = {id(e): e.dur for e in evs}
        stack: List[Event] = []
        for e in evs:
            while stack and stack[-1].end <= e.start:
                stack.pop()
            if stack and e.end <= stack[-1].end:
                own[id(stack[-1])] -= e.dur
            stack.append(e)
        return [(e, own[id(e)]) for e in evs]

    def top_ops(self, n: int = 10) -> List[List]:
        """The ops that took most device time in the window, by own time,
        named by their HLO instruction (``%name``)."""
        t0, t1 = self.window()
        agg: Dict[str, float] = {}
        for d in self.devices():
            for e, own in self.self_times(d):
                if e.start >= t0 and e.end <= t1 and own > 0:
                    key = e.name.split(" = ", 1)[0].strip()
                    agg[key] = agg.get(key, 0.0) + own / 1e9
        nd = max(len(self.devices()), 1)
        top = sorted(agg.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v / nd] for k, v in top]

    def span_at(self, t: float) -> str:
        """The innermost benchmark span open at ``t`` (not the window)."""
        best: Optional[Event] = None
        for s in self.spans:
            if s.name == WINDOW_SPAN or not (s.start <= t <= s.end):
                continue
            if best is None or s.dur < best.dur:
                best = s
        return best.name if best is not None else "no span"

    def idle_gaps(self, n: int = 10, device: Optional[int] = None):
        """The longest device-idle gaps in the window, each named by the
        benchmark span open on the host at its middle.  Returns
        ``[[name, seconds], ...]`` (one entry per gap, longest first) and
        the per-name total of all gaps."""
        t0, t1 = self.window()
        devs = self.devices() if device is None else [device]
        gaps: List[Tuple[float, float]] = []
        for d in devs:
            prev = t0
            for s, e in self.busy_intervals(d, t0, t1):
                if s > prev:
                    gaps.append((prev, s))
                prev = max(prev, e)
            if t1 > prev:
                gaps.append((prev, t1))
        named = [[self.span_at((s + e) / 2), (e - s) / 1e9] for s, e in gaps]
        named.sort(key=lambda kv: -kv[1])
        totals: Dict[str, float] = {}
        for k, v in named:
            totals[k] = totals.get(k, 0.0) + v
        return named[:n], totals
