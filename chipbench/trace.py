"""Reduction of a JAX profiler trace to the numbers the benchmark
reports: the device's busy time (the union of the intervals in which an
operation ran), its idle gaps, the time of each kernel by name, and the
device operations that took most time.

The trace is read with ``jax.profiler.ProfileData`` alone. Device planes
are those named ``/device:<accelerator>:<n>``; on each, the line of
XLA operations carries one event per operation. Host spans that the
benchmark records around its calls into the program (``jax.profiler.
TraceAnnotation``) sit on the host plane and name the idle gaps they
cover.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

OPS_LINES = ("XLA Ops",)
SKIP_LINES = ("XLA Modules", "Steps", "XLA TraceMe", "Framework Ops",
              "Framework Name Scope", "Source code")
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.trace_window"   # the traced interval, on the trace's clock


@dataclass
class Op:
    start: int          # ns
    end: int            # ns
    name: str
    text: str           # name and every string stat, for matching


@dataclass
class Trace:
    devices: Dict[str, List[Op]]
    spans: List[Tuple[int, int, str]]
    window_s: float
    t0_ns: int = 0
    t1_ns: int = 0
    by_device_busy: Dict[str, float] = field(default_factory=dict)

    # ---- busy and idle ----------------------------------------------------
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        if not self.devices:
            return 0.0
        tot = [sum(e - s for s, e in _merge(ops)) for ops in
               self.devices.values()]
        return sum(tot) / len(tot) / 1e9

    def idle_gaps(self, top: int = 10) -> List[Tuple[str, float]]:
        """The longest gaps between operations on the first device within
        the traced interval, each named by the innermost benchmark span
        (other than the interval's own) around its middle."""
        if not self.devices:
            return []
        ops = self.devices[sorted(self.devices)[0]]
        merged = _merge(ops)
        gaps = []
        edges = [(self.t0_ns, self.t0_ns)] + merged + \
            [(self.t1_ns, self.t1_ns)] if self.t1_ns else merged
        for (_, a), (b, _) in zip(edges, edges[1:]):
            if b > a:
                gaps.append((b - a, a, b))
        gaps.sort(reverse=True)
        out = []
        for dur, a, b in gaps[:top]:
            out.append((self._span_at((a + b) // 2), dur / 1e9))
        return out

    def _span_at(self, t: int) -> str:
        best = None
        for s, e, name in self.spans:
            if name == WINDOW_SPAN:
                continue
            if s <= t <= e and (best is None or e - s < best[0]):
                best = (e - s, name)
        return best[1] if best else "no benchmark span"

    # ---- kernels and operations --------------------------------------------
    def op_seconds(self, pattern: str) -> float:
        """Summed device seconds of the operations whose name or stats
        match ``pattern``, averaged over the devices."""
        rx = re.compile(pattern)
        if not self.devices:
            return 0.0
        tot = [sum(o.end - o.start for o in ops if rx.search(o.text))
               for ops in self.devices.values()]
        return sum(tot) / len(tot) / 1e9

    def top_ops(self, top: int = 10) -> List[Tuple[str, float]]:
        acc: Dict[str, float] = defaultdict(float)
        n = max(1, len(self.devices))
        for ops in self.devices.values():
            for o in ops:
                acc[o.name] += (o.end - o.start) / 1e9 / n
        return sorted(acc.items(), key=lambda kv: -kv[1])[:top]


def _merge(ops: List[Op]) -> List[Tuple[int, int]]:
    iv = sorted((o.start, o.end) for o in ops)
    out: List[Tuple[int, int]] = []
    for s, e in iv:
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _stat_text(ev) -> str:
    parts = [ev.name]
    try:
        for st in ev.stats:
            v = st[1] if isinstance(st, tuple) else getattr(st, "value", "")
            if isinstance(v, str):
                parts.append(v)
    except Exception:
        pass
    return " ".join(parts)


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return found[-1] if found else None


def read(path: str, window_s: float, t0_ns: int = 0,
         t1_ns: int = 0) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[str, List[Op]] = {}
    spans: List[Tuple[int, int, str]] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and \
                not plane.name.startswith("/device:CPU"):
            ops: List[Op] = []
            lines = list(plane.lines)
            use = [l for l in lines if l.name in OPS_LINES] or \
                [l for l in lines if l.name not in SKIP_LINES]
            for line in use:
                for ev in line.events:
                    s = int(ev.start_ns)
                    ops.append(Op(s, s + int(ev.duration_ns), ev.name,
                                  _stat_text(ev)))
            if ops:
                devices[plane.name] = ops
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        s = int(ev.start_ns)
                        spans.append((s, s + int(ev.duration_ns), ev.name))
    tr = Trace(devices=devices, spans=spans, window_s=window_s,
               t0_ns=t0_ns, t1_ns=t1_ns)
    win = [(s, e) for s, e, n in spans if n == WINDOW_SPAN]
    if win and not t1_ns:
        tr.t0_ns, tr.t1_ns = win[0]
        tr.window_s = tr.window_s or (win[0][1] - win[0][0]) / 1e9
    elif devices and not t1_ns:
        starts = [o.start for ops in devices.values() for o in ops]
        ends = [o.end for ops in devices.values() for o in ops]
        tr.t0_ns, tr.t1_ns = min(starts), max(ends)
    return tr


def describe(tr: Trace, top: int = 30) -> List[Tuple[str, float, str]]:
    """The operations that took most device time on the first device,
    with the text their stats carry (to see how kernels are named)."""
    if not tr.devices:
        return []
    ops = tr.devices[sorted(tr.devices)[0]]
    acc: Dict[str, list] = {}
    for o in ops:
        a = acc.setdefault(o.name, [0.0, o.text])
        a[0] += (o.end - o.start) / 1e9
    rows = sorted(acc.items(), key=lambda kv: -kv[1][0])[:top]
    return [(n, v[0], v[1]) for n, v in rows]
