"""The program's own spans and device scopes in a JAX profiler trace.

    python3 -m bench.program_trace <trace dir or .xplane.pb> [--gaps N]

run from the root of the repo, prints one JSON object: the traced
stretch, device busy time, each device scope's share of it and the share
under no scope, the medians of the chunk boundary and of a decode tick's
host time, and the longest idle gaps, each with its offset into the
stretch and the innermost span open at its middle.

``ProgramTrace`` extends the benchmark's reduction (``bench/trace.py``,
whose ``Trace`` reads only the benchmark's ``bench.*`` spans) with what
``repro.runtime.tracing`` writes:

* program spans: host events named ``train.*``, ``serve.*`` or
  ``host.gc``, with their arguments as stats;
* framework names: each device op's ``tf_op`` (``jit(step_fn)/while/body/
  jit(decode_attn)/pad``), read from the event metadata of the
  ``.xplane.pb``, which ``ProfileData`` does not expose, by a small reader
  of the protobuf wire format.  The program's device scopes
  (``repro.runtime.tracing.scope``) show there as ``jit(<scope>)``.

The benchmark's drivers load ``bench.trace.Trace``; no metric reads this
module yet.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import re
import sys
from typing import Dict, Iterator, List, Optional, Tuple

from bench.harness import nearest_rank
from bench.trace import WINDOW_SPAN, Event, Trace, find_xplane

PROGRAM_SPAN_PREFIXES = ("train.", "serve.")
GC_SPAN = "host.gc"
OPS_LINE = "XLA Ops"
SCOPES = ("ghost_norm_pass", "ghost_grad_pass", "dp_noise", "opt_update",
          "quantize", "attn_proj", "kv_write", "decode_attn", "mlp",
          "lm_head")


def is_program_span(name: str) -> bool:
    return name.startswith(PROGRAM_SPAN_PREFIXES) or name == GC_SPAN


# ---------------------------------------------------------------------- #
# framework names, from the protobuf wire format of XSpace (tsl xplane.proto)
# ---------------------------------------------------------------------- #
def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf: bytes, start: int, end: int) -> Iterator[Tuple[int, object]]:
    """``(field number, value)`` of one message: an int for a varint, a
    ``(start, end)`` slice for a length-delimited field."""
    i = start
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire == 1:
            value, i = None, i + 8
        elif wire == 5:
            value, i = None, i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, value


def _text(buf: bytes, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _map_entries(buf: bytes, span) -> Tuple[int, object]:
    key, value = 0, None
    for num, v in _fields(buf, *span):
        if num == 1:
            key = v
        elif num == 2:
            value = v
    return key, value


def _plane_tf_ops(buf: bytes, plane) -> Tuple[str, List[Tuple[str, int]]]:
    """A plane's name and, for each event of its ``XLA Ops`` line in order,
    ``(tf_op, duration_ps)``."""
    name, lines, event_meta, stat_names = "", [], {}, {}
    for num, v in _fields(buf, *plane):
        if num == 2:                                # XPlane.name
            name = _text(buf, v)
        elif num == 3:                              # XPlane.lines
            lines.append(v)
        elif num == 4:                              # XPlane.event_metadata
            event_meta.setdefault(*_map_entries(buf, v))
        elif num == 5:                              # XPlane.stat_metadata
            sid, meta = _map_entries(buf, v)
            for n2, v2 in _fields(buf, *meta):
                if n2 == 2:                         # XStatMetadata.name
                    stat_names[sid] = _text(buf, v2)
    if not name.startswith("/device:"):
        return name, []
    tf_op_id = next((k for k, v in stat_names.items() if v == "tf_op"), None)
    framework: Dict[int, str] = {}
    for mid, meta in event_meta.items():
        for num, v in _fields(buf, *meta):
            if num != 5:                            # XEventMetadata.stats
                continue
            sid, text = None, ""
            for n2, v2 in _fields(buf, *v):
                if n2 == 1:                         # XStat.metadata_id
                    sid = v2
                elif n2 == 5:                       # XStat.str_value
                    text = _text(buf, v2)
                elif n2 == 7:                       # XStat.ref_value
                    text = stat_names.get(v2, "")
            if sid == tf_op_id and sid is not None:
                framework[mid] = text.rstrip(":")
    out: List[Tuple[str, int]] = []
    for line in lines:
        fields = dict((n, v) for n, v in _fields(buf, *line) if n != 4)
        if fields.get(2) is None or _text(buf, fields[2]) != OPS_LINE:
            continue
        for n, ev in _fields(buf, *line):
            if n != 4:                              # XLine.events
                continue
            mid = dur = 0
            for n2, v2 in _fields(buf, *ev):
                if n2 == 1:                         # XEvent.metadata_id
                    mid = v2
                elif n2 == 3:                       # XEvent.duration_ps
                    dur = v2
            out.append((framework.get(mid, ""), dur))
    return name, out


def read_tf_ops(path: str) -> Dict[str, List[Tuple[str, int]]]:
    """``{device plane name: [(tf_op, duration_ps), ...]}`` for the events
    of each device's ``XLA Ops`` line, in the file's order."""
    with open(path, "rb") as f:
        buf = f.read()
    out = {}
    for num, v in _fields(buf, 0, len(buf)):
        if num == 1:                                # XSpace.planes
            name, ops = _plane_tf_ops(buf, v)
            if ops:
                out[name] = ops
    return out


_WRAPPED = re.compile(r"^[A-Za-z_]\w*\((.*)\)$")


def under_scope(tf_op: str, scope: str) -> bool:
    """Whether a framework name lies under ``scope``: one of its path
    components is the scope, or ``jit(<scope>)``, inside any transform
    wrappers (``transpose(jvp(jit(quantize)))``, ``vmap(...)``); a scan's
    body shows as ``while/body`` components around it, and a fusion joins
    the names of what it fused with ``;``."""
    for part in re.split("[/;]", tf_op):
        while True:
            if part == scope:
                return True
            m = _WRAPPED.match(part)
            if m is None:
                break
            part = m.group(1)
    return False


@dataclasses.dataclass
class Op(Event):
    tf_op: str = ""         # framework name; empty where the trace has none


def _named(events: List[Event], tf_ops: List[Tuple[str, int]]) -> List[Op]:
    """The op events of one device with their framework names.  The
    reader's events are the same events in the same order, which their
    durations confirm; else the names are left empty."""
    same = len(events) == len(tf_ops) and all(
        abs(e.dur * 1000.0 - dur_ps) <= 1000.0
        for e, (_, dur_ps) in zip(events, tf_ops))
    return [Op(e.name, e.start, e.dur, e.stats, e.device,
               tf_ops[i][0] if same else "") for i, e in enumerate(events)]


def _device_number(plane: str) -> int:
    try:
        return int(plane.rsplit(":", 1)[1])
    except ValueError:
        return 0


class ProgramTrace(Trace):
    """``Trace`` with the program's spans (``program_spans``, in order of
    start) and each device op's framework name (``Op.tf_op``)."""

    def __init__(self, ops: List[Event], modules: List[Event],
                 spans: List[Event], program_spans: List[Event] = ()):
        super().__init__(ops, modules, spans)
        self.program_spans = sorted(program_spans, key=lambda s: s.start)

    @classmethod
    def load(cls, path: str) -> "ProgramTrace":
        from jax.profiler import ProfileData
        if os.path.isdir(path):
            path = find_xplane(path)
        base = Trace.load(path)
        names = {_device_number(p): ops
                 for p, ops in read_tf_ops(path).items()}
        ops = []
        for dev in sorted({e.device for e in base.ops}):
            ops += _named([e for e in base.ops if e.device == dev],
                          names.get(dev, []))
        program = []
        for plane in ProfileData.from_file(path).planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for ev in line.events:
                    if is_program_span(ev.name):
                        program.append(Event(
                            ev.name, float(ev.start_ns),
                            float(ev.duration_ns),
                            {str(k): v for k, v in ev.stats}))
        return cls(ops, base.modules, base.spans, program)

    # ------------------------------------------------------------------ #
    def program(self, name: str) -> List[Event]:
        """The program spans called ``name``, in order of start."""
        return [s for s in self.program_spans if s.name == name]

    def child(self, parent: Event, name: str) -> Optional[Event]:
        """The first program span called ``name`` inside ``parent``."""
        for s in self.program_spans:
            if s.name == name and parent.start <= s.start \
                    and s.end <= parent.end:
                return s
        return None

    def scope_time_s(self, scope: Optional[str]) -> Optional[float]:
        """Own device seconds of the ops in the window whose framework
        name lies under the program scope ``scope`` (``under_scope``), or
        under none of ``SCOPES`` when ``scope`` is None (ops with no
        framework name included), averaged over the devices used; None
        when no op in the window carries a framework name."""
        t0, t1 = self.window()
        named, tot, memo = False, 0.0, {"": scope is None}
        for d in self.devices():
            for e, own in self.self_times(d):
                if e.start < t0 or e.end > t1:
                    continue
                named = named or bool(e.tf_op)
                hit = memo.get(e.tf_op)
                if hit is None:
                    hit = memo[e.tf_op] = (
                        under_scope(e.tf_op, scope) if scope is not None
                        else not any(under_scope(e.tf_op, s)
                                     for s in SCOPES))
                if hit:
                    tot += own
        if not named:
            return None
        return tot / max(len(self.devices()), 1) / 1e9

    def scope_share(self, scope: Optional[str]) -> Optional[float]:
        """Percent of the window's busy time spent in ops under ``scope``
        (under no scope when None); None when the ops carry no framework
        names or none is under it."""
        t = self.scope_time_s(scope)
        busy = self.busy_s()
        if not t or busy <= 0:
            return None
        return 100.0 * t / busy

    def span_at(self, t: float) -> str:
        """The innermost benchmark or program span open at ``t`` (not the
        window)."""
        best: Optional[Event] = None
        for s in list(self.spans) + self.program_spans:
            if s.name == WINDOW_SPAN or not (s.start <= t <= s.end):
                continue
            if best is None or s.dur < best.dur:
                best = s
        return best.name if best is not None else "no span"

    def gaps_at(self, n: int = 10) -> List[List]:
        """The ``n`` longest device-idle gaps in the window, longest
        first, as ``[span, seconds, offset_s]``: the innermost span open at
        the gap's middle and the gap's start from the window's start."""
        t0, t1 = self.window()
        gaps = []
        for d in self.devices():
            prev = t0
            for s, e in self.busy_intervals(d, t0, t1):
                if s > prev:
                    gaps.append((prev, s))
                prev = max(prev, e)
            if t1 > prev:
                gaps.append((prev, t1))
        gaps.sort(key=lambda g: g[0] - g[1])
        return [[self.span_at((s + e) / 2), (e - s) / 1e9, (s - t0) / 1e9]
                for s, e in gaps[:n]]


# ---------------------------------------------------------------------- #
# numbers read from the program's spans
# ---------------------------------------------------------------------- #
def boundary_ms_p50(tr: ProgramTrace) -> Optional[float]:
    """Median host time at a chunk boundary of an epoch: for consecutive
    ``train.chunk`` spans of one epoch, the start of the next chunk's
    ``train.dispatch`` minus the end of the previous chunk's
    ``train.wait`` (reading the losses, accounting, the preemption poll,
    sampling, gathering and feeding the next chunk)."""
    chunks = tr.program("train.chunk")
    epoch_ends = tr.program("train.epoch_end")
    gaps = []
    for a, b in zip(chunks, chunks[1:]):
        if b.stats.get("step") != a.stats.get("step", 0) + a.stats.get("k", 0):
            continue
        if any(a.end <= e.start <= b.start for e in epoch_ends):
            continue
        wait, dispatch = tr.child(a, "train.wait"), tr.child(b,
                                                              "train.dispatch")
        if wait is not None and dispatch is not None:
            gaps.append((dispatch.start - wait.end) / 1e6)
    return nearest_rank(gaps, 0.5) if gaps else None


def tick_host_ms_p50(tr: ProgramTrace) -> Optional[float]:
    """Median host time of a decode tick: a ``serve.tick`` span's duration
    minus its ``serve.wait`` child (upload, dispatch and per-slot token
    bookkeeping); ticks that decoded nothing have no wait and are left
    out."""
    host = []
    for tick in tr.program("serve.tick"):
        wait = tr.child(tick, "serve.wait")
        if wait is not None:
            host.append((tick.dur - wait.dur) / 1e6)
    return nearest_rank(host, 0.5) if host else None


def summary(tr: ProgramTrace, gaps: int = 10) -> dict:
    busy = tr.busy_s()
    return {
        "window_s": tr.window_s(), "busy_s": busy,
        "scope_share": {**{s: tr.scope_share(s) for s in SCOPES},
                        "(none)": tr.scope_share(None)},
        "boundary_ms_p50": boundary_ms_p50(tr),
        "tick_host_ms_p50": tick_host_ms_p50(tr),
        "gc_ms": sum(s.dur for s in tr.program(GC_SPAN)) / 1e6,
        "idle_gaps": tr.gaps_at(gaps),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace", help="profiler output directory or .xplane.pb")
    ap.add_argument("--gaps", type=int, default=10)
    args = ap.parse_args(argv)
    print(json.dumps(summary(ProgramTrace.load(args.trace), args.gaps)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
