"""From the profiler's trace to the numbers the per-layer metrics read.

Two stages, kept apart so that the second can be tested on a small
recorded trace:

1. :func:`load` reads the ``.xplane.pb`` that ``jax.profiler.trace``
   writes and keeps two kinds of event, as plain
   ``[name, start_ns, end_ns]`` lists on one clock: the device's
   operations (the ``XLA Ops`` line of each TPU plane), and the
   benchmark's own host spans (``bench.*`` annotations).
2. The reductions: the union of the device's busy intervals, the busy
   and idle seconds of a window, the time of the operations whose names
   match a kernel, the operations that took most time, and the longest
   idle gaps, each labelled with the host span it fell in.

:func:`traced_window` drives a few rounds of a cell under the profiler
and returns what the readers in ``bench/metrics/`` use.
"""
from __future__ import annotations

import glob
import os
import re
import shutil
import time
from typing import Dict, Iterable, List, Sequence, Tuple

Event = List  # [name, start_ns, end_ns]

#: prefix of the benchmark's own host spans
SPAN = "bench."
#: the device line whose events are operations
OPS_LINE = "XLA Ops"


def load(path: str) -> Dict[str, List[Event]]:
    """``{"device": [...], "spans": [...]}`` from one ``.xplane.pb``:
    the operations of every TPU plane and the ``bench.*`` host spans."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    device, spans = [], []
    for plane in pd.planes:
        on_device = plane.name.startswith("/device:TPU:")
        for line in plane.lines:
            if on_device and line.name == OPS_LINE:
                device.extend([e.name, e.start_ns, e.start_ns + e.duration_ns]
                              for e in line.events)
            elif not on_device:
                spans.extend([e.name, e.start_ns, e.start_ns + e.duration_ns]
                             for e in line.events
                             if e.name.startswith(SPAN))
    device.sort(key=lambda e: e[1])
    spans.sort(key=lambda e: e[1])
    return {"device": device, "spans": spans}


def union(intervals: Iterable[Sequence[float]]) -> List[Tuple[float, float]]:
    """Merged, sorted ``(start, end)`` intervals."""
    out: List[List[float]] = []
    for s, e in sorted((float(a), float(b)) for a, b in intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def busy_ns(device: List[Event], lo: float, hi: float) -> float:
    """Nanoseconds of [lo, hi) in which some device operation ran."""
    return sum(e - s for s, e in clip(union((d[1], d[2]) for d in device),
                                      lo, hi))


#: "%name = shape opcode(operands)...", the trace's text of an XLA op
_HLO = re.compile(r"^(%[^ ]+) = .*? ([a-z][a-z0-9-]*)\(")
#: ops that only hold other ops, whose time their bodies' ops report
CONTAINERS = ("while", "conditional", "call")


def short(name: str) -> str:
    """``"%fusion.3 fusion"`` for the trace's full text of an op."""
    m = _HLO.match(name)
    return f"{m.group(1)} {m.group(2)}" if m else name[:120]


def top_ops(device: List[Event], k: int = 10) -> List[List]:
    """The ``k`` operations (by instruction name and opcode) with the
    most summed device time, in seconds; loops and calls, whose bodies'
    operations are listed themselves, are left out."""
    tot: Dict[str, float] = {}
    for name, s, e in device:
        key = short(name)
        if key.rsplit(" ", 1)[-1] in CONTAINERS:
            continue
        tot[key] = tot.get(key, 0.0) + (e - s)
    top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns * 1e-9] for name, ns in top]


def idle_gaps(device: List[Event], spans: List[Event], lo: float,
              hi: float, k: int = 10) -> List[List]:
    """The ``k`` longest stretches of [lo, hi) with no device operation,
    in seconds, each named by the innermost ``bench.*`` host span that
    covers its middle (``"outside bench spans"`` where none does)."""
    busy = clip(union((d[1], d[2]) for d in device), lo, hi)
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    out = []
    for s, e in gaps:
        mid = 0.5 * (s + e)
        name, length = "outside bench spans", None
        for sp in spans:  # innermost: the shortest, the later on a tie
            if sp[1] <= mid <= sp[2] and (length is None
                                          or sp[2] - sp[1] <= length):
                name, length = sp[0], sp[2] - sp[1]
        out.append([name, (e - s) * 1e-9])
    out.sort(key=lambda g: -g[1])
    return out[:k]


def reduce(events: Dict[str, List[Event]], rounds: int) -> Dict:
    """Busy and window seconds, the breakdown, and the device events,
    over the window that the ``bench.window`` span marks."""
    win = [s for s in events["spans"] if s[0] == SPAN + "window"]
    if not win:
        raise ValueError("trace holds no bench.window span")
    lo, hi = win[-1][1], win[-1][2]
    device = [d for d in events["device"] if d[2] > lo and d[1] < hi]
    return {
        "rounds": rounds,
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_ns(device, lo, hi) * 1e-9,
        "device": device,
        "breakdown": {"device_ops": top_ops(device),
                      "idle_gaps": idle_gaps(device, events["spans"], lo,
                                             hi)},
    }


def traced_window(eng, rounds: int, out_dir: str) -> Dict:
    """Run ``rounds`` rounds of ``eng`` under the profiler (host Python
    tracing off), each inside a ``bench.step_round`` span and the whole
    inside ``bench.window``, then reduce the trace."""
    import jax
    shutil.rmtree(out_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    t0 = time.perf_counter()
    with jax.profiler.trace(out_dir, profiler_options=opts):
        with jax.profiler.TraceAnnotation(SPAN + "window"):
            for _ in range(rounds):
                with jax.profiler.TraceAnnotation(SPAN + "step_round"):
                    eng.step()
            with jax.profiler.TraceAnnotation(SPAN + "finish"):
                eng.finish()
    paths = glob.glob(os.path.join(out_dir, "**", "*.xplane.pb"),
                      recursive=True)
    t1 = time.perf_counter()
    out = reduce(load(max(paths, key=os.path.getmtime)), rounds)
    out["read_s"] = time.perf_counter() - t1
    out["traced_s"] = t1 - t0
    return out
