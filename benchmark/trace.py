"""From a JAX profiler trace (XPlane) to device time per program span.

The trace holds the host threads, with the program's spans
(``jax.profiler.TraceAnnotation``, named like ``sort_bam.read``) and the
``PjitFunction(<name>)`` launch events, and one plane per TPU, with an
``XLA Modules`` line (one event per program run, ``jit_<name>(<id>)``) and
an ``XLA Ops`` line.  Host and device share the profiler's clock.

- Each program run is matched to its launch (the launches of one program,
  in order, to its runs on each device, in order) and takes the program
  spans open on the launching thread at that moment.
- Busy time is the union of the ``XLA Ops`` intervals inside the window;
  each idle gap goes to the innermost program span open at its middle.
"""

from __future__ import annotations

import bisect
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

#: The benchmark's own span around the traced job.
JOB_SPAN = "benchmark.job"
#: Program spans: dotted lower-case names (``flate.stage.inflate_device``).
_SPAN_RE = re.compile(r"^[a-z][a-z0-9_]*(\.[a-z0-9_]+)+$")
_LAUNCH_RE = re.compile(r"^PjitFunction\((.+)\)$")
_MODULE_RE = re.compile(r"^jit_(.+)\(\d+\)$")
_DEVICE_RE = re.compile(r"^/device:TPU:(\d+)$")


@dataclass
class Span:
    name: str
    start: int
    end: int
    depth: int


@dataclass
class Run:
    """One program run on one device."""

    device: int
    program: str
    start: int
    end: int
    spans: Tuple[str, ...] = ()  # open at its launch, outermost first


@dataclass
class Reduced:
    window: Tuple[int, int]
    devices: List[int]
    runs: List[Run]
    busy_ns: Dict[int, int]
    gaps: Dict[str, int] = field(default_factory=dict)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def busy_s(self) -> float:
        """Busy seconds, averaged over the devices."""
        return sum(self.busy_ns.values()) / len(self.busy_ns) / 1e9 if self.busy_ns else 0.0

    def device_s(self, under: Sequence[str], device: Optional[int] = None) -> Dict[int, float]:
        """Seconds of program runs launched inside any span named in
        ``under``, per device."""
        out = {d: 0.0 for d in self.devices}
        for r in self.runs:
            if (device is None or r.device == device) and any(s in r.spans for s in under):
                out[r.device] += (r.end - r.start) / 1e9
        return out

    def top_runs(self, k: int = 10) -> List[List]:
        """``[innermost span:program, seconds]``, summed over devices."""
        tot: Dict[str, float] = defaultdict(float)
        for r in self.runs:
            inner = next((s for s in reversed(r.spans) if s != JOB_SPAN), "(no span)")
            tot[f"{inner}:jit_{r.program}"] += (r.end - r.start) / 1e9
        return [[n, s] for n, s in sorted(tot.items(), key=lambda kv: -kv[1])[:k]]

    def top_gaps(self, k: int = 10) -> List[List]:
        return [[n, ns / 1e9] for n, ns in sorted(self.gaps.items(), key=lambda kv: -kv[1])[:k]]


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _host_lines(profile):
    for plane in profile.planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                yield list(line.events)


def _spans_and_launches(profile):
    """Per host thread: its program spans (nesting depth set) and its
    launches as ``(program, start)``."""
    threads = []
    for events in _host_lines(profile):
        spans, launches = [], []
        for e in events:
            start, end = int(e.start_ns), int(e.start_ns + e.duration_ns)
            m = _LAUNCH_RE.match(e.name)
            if m:
                launches.append((m.group(1), start, end))
            elif _SPAN_RE.match(e.name):
                spans.append(Span(e.name, start, end, 0))
        spans.sort(key=lambda s: (s.start, -s.end))
        stack: List[Span] = []
        for s in spans:
            while stack and stack[-1].end <= s.start:
                stack.pop()
            s.depth = len(stack)
            stack.append(s)
        # A launch can show twice, nested; keep the outer one.
        launches.sort(key=lambda x: (x[1], -x[2]))
        outer = []
        for p, a, b in launches:
            if outer and outer[-1][0] == p and a < outer[-1][2] and b <= outer[-1][2]:
                continue
            outer.append((p, a, b))
        threads.append((spans, outer))
    return threads


def _open_at(spans: List[Span], starts: List[int], t: int) -> Tuple[str, ...]:
    i = bisect.bisect_right(starts, t)
    return tuple(s.name for s in sorted(
        (s for s in spans[:i] if s.end > t), key=lambda s: s.depth))


def reduce(profile, window: Optional[Tuple[int, int]] = None) -> Reduced:
    """Reduces a loaded ``jax.profiler.ProfileData``.  ``window`` defaults
    to the ``benchmark.job`` span."""
    threads = _spans_and_launches(profile)
    if window is None:
        jobs = [s for spans, _ in threads for s in spans if s.name == JOB_SPAN]
        if not jobs:
            raise ValueError(f"no {JOB_SPAN} span in the trace")
        window = (min(s.start for s in jobs), max(s.end for s in jobs))
    w0, w1 = window
    launches: Dict[str, List[Tuple[int, int]]] = defaultdict(list)  # program -> [(start, thread)]
    for ti, (_, ls) in enumerate(threads):
        for p, a, _b in ls:
            launches[p].append((a, ti))
    for v in launches.values():
        v.sort()
    starts = [[s.start for s in spans] for spans, _ in threads]

    runs: List[Run] = []
    busy: Dict[int, int] = {}
    unions: Dict[int, List[Tuple[int, int]]] = {}
    devices: List[int] = []
    for plane in profile.planes:
        m = _DEVICE_RE.match(plane.name)
        if not m:
            continue
        dev = int(m.group(1))
        ops, mods = [], []
        for line in plane.lines:
            if line.name == "XLA Ops":
                ops = [(int(e.start_ns), int(e.start_ns + e.duration_ns)) for e in line.events]
            elif line.name == "XLA Modules":
                mods = [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns)) for e in line.events]
        if not mods and not ops:
            continue
        devices.append(dev)
        by_prog: Dict[str, List[Tuple[int, int]]] = defaultdict(list)
        for name, a, b in sorted(mods, key=lambda x: x[1]):
            mm = _MODULE_RE.match(name)
            by_prog[mm.group(1) if mm else name].append((a, b))
        for prog, rs in by_prog.items():
            ls = launches.get(prog, [])
            for i, (a, b) in enumerate(rs):
                if len(ls) == len(rs):
                    _, ti = ls[i]
                    t = ls[i][0]
                else:  # the latest launch before the run began
                    j = bisect.bisect_right(ls, (a, 1 << 62)) - 1
                    if j < 0:
                        runs.append(Run(dev, prog, a, b))
                        continue
                    t, ti = ls[j]
                spans, _ = threads[ti]
                runs.append(Run(dev, prog, a, b, _open_at(spans, starts[ti], t)))
        clipped = [(max(a, w0), min(b, w1)) for a, b in (ops or [(a, b) for _, a, b in mods])]
        unions[dev] = _union([c for c in clipped if c[1] > c[0]])
        busy[dev] = sum(b - a for a, b in unions[dev])
    runs = [r for r in runs if r.end > w0 and r.start < w1]
    red = Reduced(window=window, devices=sorted(devices), runs=runs, busy_ns=busy)
    if devices:
        bounds, labels = _innermost([s for spans, _ in threads for s in spans if s.name != JOB_SPAN])
        for a, b in _gaps(unions[red.devices[0]], w0, w1):
            i = bisect.bisect_right(bounds, (a + b) // 2) - 1
            name = labels[i] if i >= 0 else "(no span)"
            red.gaps[name] = red.gaps.get(name, 0) + (b - a)
    return red


def _innermost(spans: List[Span]) -> Tuple[List[int], List[str]]:
    """Boundaries and, from each one on, the name of the innermost open
    span (deepest, then latest started; any thread)."""
    edges = sorted([(s.start, 1, i) for i, s in enumerate(spans)] + [(s.end, 0, i) for i, s in enumerate(spans)])
    active: Dict[int, Span] = {}
    bounds: List[int] = []
    labels: List[str] = []
    for t, opening, i in edges:
        if opening:
            active[i] = spans[i]
        else:
            active.pop(i, None)
        best = max(active.values(), key=lambda s: (s.depth, s.start)).name if active else "(no span)"
        if bounds and bounds[-1] == t:
            labels[-1] = best
        else:
            bounds.append(t)
            labels.append(best)
    return bounds, labels


def _gaps(busy: List[Tuple[int, int]], w0: int, w1: int) -> List[Tuple[int, int]]:
    out, t = [], w0
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if w1 > t:
        out.append((t, w1))
    return out


def load(path: str):
    import jax

    return jax.profiler.ProfileData.from_file(path)


def load_serialized(data: bytes):
    import jax

    return jax.profiler.ProfileData.from_serialized_xspace(data)
