"""The program's own spans in a profiler trace: where the device idles.

    python3 -m chipbench.phases --workload <cell> --seed <n> \
        --seconds <run_seconds> [--keep <dir>]

runs one traced run of a cell (``chipbench.run`` with ``--trace 1``,
whose result line it prints first), reads the same trace again for the
program's ``cc.*`` spans (``repro.tracing``) and the device's "XLA
Modules" events, and prints one more JSON line: the device-idle seconds
of the traced interval by the innermost span around each idle instant,
the ten longest idle gaps named among the ``bench.*`` and ``cc.*``
spans, span and module counts and times, the numbers below, and the
cost of one span with the profiler off and on. ``--keep`` copies the
trace file there.

- ``idle_prefill_ms``: device-idle time under the ``cc.prefill`` spans
  that lie wholly in the traced interval, over the requests they
  prefilled (their ``n``).
- ``idle_decode_ms``: device-idle time under whole ``cc.decode`` spans,
  per span.
- ``decode_step_device_ms``: duration of the ``jit_paged_decode_step``
  module events in the interval, per event.

The run's result line is the benchmark's, unchanged: these numbers are
read here from a trace that ``chipbench.run`` reads only for its own
spans and operations.
"""
from __future__ import annotations

import argparse
import bisect
import json
import shutil
import sys
import time
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

from chipbench import trace as T

PREFIXES = ("cc.", "bench.")
MODULES_LINE = "XLA Modules"
NO_SPAN = "no span"


@dataclass
class Span:
    start: int              # ns, on the trace's clock
    end: int
    name: str               # up to any "#"
    args: Dict[str, object] = field(default_factory=dict)


@dataclass
class Phases:
    spans: List[Span]
    modules: Dict[str, List[Tuple[int, int, str]]]   # device -> events


def _args(ev) -> Dict[str, object]:
    out = {}
    for st in ev.stats:
        k, v = st if isinstance(st, tuple) else (st.name, st.value)
        if isinstance(v, (int, str)):
            out[k] = v
    return out


def read(path: str) -> Phases:
    """The ``cc.*`` and ``bench.*`` host spans, with their int and string
    args, and each device's "XLA Modules" events (named without the
    program id)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    spans: List[Span] = []
    modules: Dict[str, List[Tuple[int, int, str]]] = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:") and \
                not plane.name.startswith("/device:CPU"):
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    modules[plane.name] = [
                        (int(ev.start_ns),
                         int(ev.start_ns) + int(ev.duration_ns),
                         ev.name.split("(")[0]) for ev in line.events]
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    name = ev.name.split("#")[0]
                    if name.startswith(PREFIXES):
                        s = int(ev.start_ns)
                        spans.append(Span(s, s + int(ev.duration_ns), name,
                                          _args(ev)))
    return Phases(spans=spans, modules=modules)


def _key(s: Span):
    """Innermost first: the shorter span, and a program span on a tie."""
    return (s.end - s.start, not s.name.startswith("cc."))


def _timeline(spans: List[Span]) -> Tuple[List[int], List[str]]:
    """The edges of every span, and for each interval between two
    neighbouring edges the name of the innermost span around it (the
    traced interval's own span left out)."""
    spans = sorted((s for s in spans if s.name != T.WINDOW_SPAN),
                   key=lambda s: s.start)
    edges = sorted({x for s in spans for x in (s.start, s.end)})
    names: List[str] = []
    active: List[Span] = []
    j = 0
    for x in edges[:-1]:
        while j < len(spans) and spans[j].start <= x:
            active.append(spans[j])
            j += 1
        active = [s for s in active if s.end > x]
        inner = min(active, key=_key, default=None)
        names.append(inner.name if inner else NO_SPAN)
    return edges, names


def idle_intervals(tr: T.Trace) -> List[Tuple[int, int]]:
    """Gaps between operations on the first device, within the traced
    interval, in order."""
    if not tr.devices:
        return []
    merged = T._merge(tr.devices[sorted(tr.devices)[0]])
    edges = [(tr.t0_ns, tr.t0_ns)] + \
        [(max(s, tr.t0_ns), min(e, tr.t1_ns)) for s, e in merged
         if e > tr.t0_ns and s < tr.t1_ns] + [(tr.t1_ns, tr.t1_ns)]
    return [(a, b) for (_, a), (b, _) in zip(edges, edges[1:]) if b > a]


def idle_by_span(tr: T.Trace, spans: List[Span]) -> Dict[str, float]:
    """Device-idle seconds of the traced interval, each idle instant
    given to the innermost span around it."""
    edges, names = _timeline(spans)
    out: Dict[str, float] = {}
    for a, b in idle_intervals(tr):
        t, i = a, bisect.bisect_right(edges, a) - 1
        while t < b:
            if 0 <= i < len(names):
                nxt, name = edges[i + 1], names[i]
            else:
                nxt = edges[0] if i < 0 and edges else b
                name = NO_SPAN
            nxt = min(nxt, b)
            out[name] = out.get(name, 0.0) + (nxt - t) / 1e9
            t, i = nxt, i + 1
    return out


def idle_under(tr: T.Trace, spans: List[Span]) -> float:
    """Device-idle seconds inside the given spans."""
    gaps = idle_intervals(tr)
    starts = [a for a, _ in gaps]
    tot = 0
    for s in spans:
        i = max(0, bisect.bisect_right(starts, s.start) - 1)
        while i < len(gaps) and gaps[i][0] < s.end:
            a, b = gaps[i]
            tot += max(0, min(b, s.end) - max(a, s.start))
            i += 1
    return tot / 1e9


def leaves(spans: List[Span]) -> set:
    """Names of the program spans that never hold another program span
    (spans of one thread nest)."""
    cc = sorted((s for s in spans if s.name.startswith("cc.")),
                key=lambda s: (s.start, -s.end))
    parents = {a.name for a, b in zip(cc, cc[1:]) if b.start < a.end}
    return {s.name for s in cc} - parents


def whole(tr: T.Trace, spans: List[Span], name: str) -> List[Span]:
    return [s for s in spans if s.name == name and
            tr.t0_ns <= s.start and s.end <= tr.t1_ns]


def idle_gaps(tr: T.Trace, spans: List[Span], top: int = 10):
    """``Trace.idle_gaps`` with the program's spans beside the
    benchmark's: the same gaps, named by the innermost span around each
    gap's middle."""
    named = [(s.start, s.end, s.name) for s in
             sorted(spans, key=lambda s: not s.name.startswith("cc."))]
    return T.Trace(devices=tr.devices, spans=named, window_s=tr.window_s,
                   t0_ns=tr.t0_ns, t1_ns=tr.t1_ns).idle_gaps(top)


def module_events(tr: T.Trace, ph: Phases, name: str):
    """Durations (s) of the first device's module events called ``name``
    that lie wholly in the traced interval."""
    if not ph.modules:
        return []
    evs = ph.modules[sorted(ph.modules)[0]]
    return [(e - s) / 1e9 for s, e, n in evs
            if n == name and tr.t0_ns <= s and e <= tr.t1_ns]


def report(tr: T.Trace, ph: Phases) -> dict:
    """Everything the command prints about one trace."""
    cc = [s for s in ph.spans if s.name.startswith("cc.")]
    inside = [s for s in cc if tr.t0_ns <= s.start and s.end <= tr.t1_ns]
    by_span = idle_by_span(tr, ph.spans)
    idle = sum(by_span.values())
    leaf = leaves(ph.spans)
    out: dict = {"window_s": tr.window_s, "idle_s": idle,
                 "idle_cc_leaf_share": sum(v for k, v in by_span.items()
                                           if k in leaf) / idle
                 if idle else None,
                 "idle_by_span": dict(sorted(by_span.items(),
                                             key=lambda kv: -kv[1]))}
    counts: Dict[str, List[float]] = {}
    for s in inside:
        c = counts.setdefault(s.name, [0, 0.0])
        c[0] += 1
        c[1] += (s.end - s.start) / 1e9
    out["spans"] = dict(sorted(counts.items()))
    out["spans_per_s"] = len(inside) / tr.window_s if tr.window_s else None
    pre = whole(tr, cc, "cc.prefill")
    n = sum(int(s.args.get("n", 0)) for s in pre)
    out["idle_prefill_ms"] = 1e3 * idle_under(tr, pre) / n if n else None
    dec = whole(tr, cc, "cc.decode")
    out["idle_decode_ms"] = \
        1e3 * idle_under(tr, dec) / len(dec) if dec else None
    steps = module_events(tr, ph, "jit_paged_decode_step")
    out["decode_step_device_ms"] = \
        1e3 * sum(steps) / len(steps) if steps else None
    mods: Dict[str, List[float]] = {}
    for name in {n for evs in ph.modules.values() for _, _, n in evs}:
        d = module_events(tr, ph, name)
        if d:
            mods[name] = [len(d), sum(d)]
    out["modules"] = dict(sorted(mods.items(), key=lambda kv: -kv[1][1]))
    out["idle_gaps"] = [list(g) for g in idle_gaps(tr, ph.spans)]
    return out


def span_cost_us(n: int = 20000) -> Dict[str, float]:
    """Microseconds per span (a ``TraceAnnotation`` with one int arg,
    entered and left), with the profiler off and on."""
    import tempfile
    import jax

    def per_span():
        t = time.perf_counter()
        for i in range(n):
            with jax.profiler.TraceAnnotation("cc.cost", b=i):
                pass
        return (time.perf_counter() - t) / n * 1e6
    off = per_span()
    d = tempfile.mkdtemp(prefix="chipbench-cost-")
    jax.profiler.start_trace(d)
    try:
        on = per_span()
    finally:
        jax.profiler.stop_trace()
        shutil.rmtree(d, ignore_errors=True)
    return {"off": off, "on": on}


def traced_run(workload: str, seed: int, seconds: float,
               keep: str = None, **run_kw) -> Tuple[dict, dict]:
    """One ``--trace 1`` run of a cell (``run_kw`` as for
    ``chipbench.run.run_cell``): its result, and ``report`` of its
    trace."""
    from chipbench import run as R
    # run_cell reads the trace through chipbench.trace.read and then
    # deletes it: read the program's spans from the same file first
    seen: dict = {}
    read_trace = T.read

    def read_both(path, *a, **kw):
        seen["phases"] = read(path)
        if keep:
            shutil.copy(path, keep)
        seen["trace"] = tr = read_trace(path, *a, **kw)
        return tr
    T.read = read_both
    try:
        result = R.run_cell(workload, seed, seconds, True, **run_kw)
    finally:
        T.read = read_trace
    return result, report(seen["trace"], seen["phases"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", default=None,
                    help="directory to copy the trace file into")
    args = ap.parse_args(argv)
    from chipbench.run import BenchError
    try:
        _result, out = traced_run(args.workload, args.seed, args.seconds,
                                  args.keep)
    except BenchError as e:
        print(f"chipbench: {e}", file=sys.stderr, flush=True)
        return 2
    out["span_cost_us"] = span_cost_us()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
