"""The program's own names in the traced window: device time under each
``jax.named_scope`` and the ``repro.*`` host spans.

``bench/trace.py`` reads the ``.xplane.pb`` through JAX's
``ProfileData``, which gives each event its name and its own stats but
not the stats of the event's metadata, where a TPU trace keeps an
operation's name stack (``tf_op``: ``jit(f)/while/body/local_steps/...``).
This module parses the same file with protobuf, from the public XSpace
schema (``tsl/profiler/protobuf/xplane.proto``, the fields read here),
and keeps, on the trace's clock in nanoseconds:

- ``ops``: ``[text, start, end, name_stack]`` for each operation of the
  ``XLA Ops`` line of every TPU plane;
- ``spans``: ``[name, start, end]`` for each host span named
  ``bench.*`` (the harness's) or ``repro.*`` (the program's,
  ``repro/core/spans.py``).

Readers in ``bench/metrics/`` take their numbers per traced round from
the window the ``bench.window`` span marks: the device time of a scope
is the union of the intervals of its operations, loops and calls left
out (their bodies' operations are counted themselves); a span's time is
the sum of its host intervals. The trace read is the newest one the
harness wrote for the cell (``.bench_out/trace/<cell>``). Run as a
script it prints a trace's scope times and its longest idle gaps, each
named by the innermost host span of either kind that covers it:

  python3 bench/scopes.py .bench_out/trace/<cell>
"""
from __future__ import annotations

import functools
import glob
import os
import sys
from typing import Dict, Iterable, List, Optional, Tuple

if __package__ in (None, ""):  # run as a script
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from bench import trace as tr  # noqa: E402

#: prefixes of the host spans kept
SPANS = ("bench.", "repro.")
#: the metadata stat that holds a device operation's name stack
STACK_STAT = "tf_op"


@functools.lru_cache(maxsize=None)
def _schema():
    """The message class of the parts of XSpace read here (proto3; maps
    in their wire form, repeated key/value entries; strings as bytes)."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory
    F = descriptor_pb2.FieldDescriptorProto
    fd = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane.proto", package="bench_xplane", syntax="proto3")

    def msg(name, *fields):
        m = fd.message_type.add(name=name)
        for fname, num, typ, rep, sub in fields:
            f = m.field.add(name=fname, number=num, type=typ,
                            label=F.LABEL_REPEATED if rep
                            else F.LABEL_OPTIONAL)
            if sub:
                f.type_name = ".bench_xplane." + sub

    I64, U64, DBL = F.TYPE_INT64, F.TYPE_UINT64, F.TYPE_DOUBLE
    BYT, MSG = F.TYPE_BYTES, F.TYPE_MESSAGE
    msg("XStat", ("metadata_id", 1, I64, 0, None),
        ("double_value", 2, DBL, 0, None), ("uint64_value", 3, U64, 0, None),
        ("int64_value", 4, I64, 0, None), ("str_value", 5, BYT, 0, None),
        ("bytes_value", 6, BYT, 0, None), ("ref_value", 7, U64, 0, None))
    msg("XEvent", ("metadata_id", 1, I64, 0, None),
        ("offset_ps", 2, I64, 0, None), ("duration_ps", 3, I64, 0, None),
        ("stats", 4, MSG, 1, "XStat"))
    msg("XLine", ("id", 1, I64, 0, None), ("name", 2, BYT, 0, None),
        ("timestamp_ns", 3, I64, 0, None), ("events", 4, MSG, 1, "XEvent"))
    msg("XEventMetadata", ("id", 1, I64, 0, None), ("name", 2, BYT, 0, None),
        ("display_name", 4, BYT, 0, None), ("stats", 5, MSG, 1, "XStat"))
    msg("XStatMetadata", ("id", 1, I64, 0, None), ("name", 2, BYT, 0, None))
    msg("EventMetadataEntry", ("key", 1, I64, 0, None),
        ("value", 2, MSG, 0, "XEventMetadata"))
    msg("StatMetadataEntry", ("key", 1, I64, 0, None),
        ("value", 2, MSG, 0, "XStatMetadata"))
    msg("XPlane", ("id", 1, I64, 0, None), ("name", 2, BYT, 0, None),
        ("lines", 3, MSG, 1, "XLine"),
        ("event_metadata", 4, MSG, 1, "EventMetadataEntry"),
        ("stat_metadata", 5, MSG, 1, "StatMetadataEntry"))
    msg("XSpace", ("planes", 1, MSG, 1, "XPlane"))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))


def _text(b: bytes) -> str:
    return b.decode("utf-8", "replace")


def _stat_text(st, names: Dict[int, str]) -> Optional[str]:
    if st.ref_value:
        return names.get(st.ref_value)
    if st.str_value:
        return _text(st.str_value)
    return None


def load(path: str) -> Dict[str, List]:
    """``{"ops": [...], "spans": [...]}`` from one ``.xplane.pb``. An
    operation's name stack is its metadata's ``tf_op`` stat (``""``
    where XLA made the operation itself, as a layout copy)."""
    space = _schema()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    ops, spans = [], []
    for plane in space.planes:
        names = {e.key: _text(e.value.name) for e in plane.stat_metadata}
        meta = {e.key: e.value for e in plane.event_metadata}
        on_device = _text(plane.name).startswith("/device:TPU:")
        for line in plane.lines:
            if on_device and _text(line.name) != tr.OPS_LINE:
                continue
            t0 = line.timestamp_ns
            stacks: Dict[int, str] = {}
            for ev in line.events:
                md = meta.get(ev.metadata_id)
                if md is None:
                    continue
                start = t0 + ev.offset_ps / 1000.0
                end = start + ev.duration_ps / 1000.0
                if on_device:
                    if ev.metadata_id not in stacks:
                        stacks[ev.metadata_id] = next(
                            (_stat_text(st, names) or "" for st in md.stats
                             if names.get(st.metadata_id) == STACK_STAT),
                            "")
                    ops.append([_text(md.name), start, end,
                                stacks[ev.metadata_id]])
                elif _text(md.name).startswith(SPANS):
                    spans.append([_text(md.name), start, end])
    ops.sort(key=lambda e: e[1])
    spans.sort(key=lambda e: e[1])
    return {"ops": ops, "spans": spans}


def in_scope(stack: str, scopes: Iterable[str]) -> bool:
    """Whether a name stack holds one of ``scopes`` as a component."""
    parts = stack.split("/")
    return any(s in parts for s in scopes)


def _opcode(text: str) -> str:
    return tr.short(text).rsplit(" ", 1)[-1]


def scope_ns(ops: List, scopes: Iterable[str], lo: float,
             hi: float) -> float:
    """Nanoseconds of [lo, hi) in which some operation under one of
    ``scopes`` ran; loops and calls are left out."""
    scopes = tuple(scopes)
    iv = [(s, e) for text, s, e, stack in ops
          if in_scope(stack, scopes) and _opcode(text) not in tr.CONTAINERS]
    return sum(e - s for s, e in tr.clip(tr.union(iv), lo, hi))


def span_ns(spans: List, name: str, lo: float, hi: float) -> float:
    """Summed nanoseconds of the host spans named ``name`` in [lo, hi)."""
    return sum(e - s for n, s0, e0 in spans if n == name
               for s, e in tr.clip([(s0, e0)], lo, hi))


def window(events: Dict[str, List]) -> Tuple[float, float]:
    """The last ``bench.window`` span's [start, end)."""
    win = [s for s in events["spans"] if s[0] == tr.SPAN + "window"]
    if not win:
        raise ValueError("trace holds no bench.window span")
    return win[-1][1], win[-1][2]


def newest_xplane(path: str) -> Optional[str]:
    """The newest ``.xplane.pb`` at or under ``path``."""
    if os.path.isfile(path):
        return path
    found = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def run_events(run: Dict) -> Optional[Dict[str, List]]:
    """The events of a chip run's traced window, read once per run (kept
    in ``run["trace"]``), or None where the run traced nothing or ran
    without a chip's peaks (a CPU rehearsal, where no per-layer time is
    read)."""
    from bench import harness
    t = run.get("trace")
    if not t or not run.get("peaks") or "cell" not in run:
        return None
    if "scopes" not in t:
        path = newest_xplane(os.path.join(harness.OUT, "trace",
                                          run["cell"]))
        t["scopes"] = None if path is None else load(path)
    return t["scopes"]


def _per_round_ms(run: Dict, ns_of) -> Optional[float]:
    ev = run_events(run)
    if ev is None:
        return None
    lo, hi = window(ev)
    ns = ns_of(ev, lo, hi)
    return 1e-6 * ns / run["trace"]["rounds"] if ns else None


def scope_ms(run: Dict, scopes: Iterable[str]) -> Optional[float]:
    """Device milliseconds per traced round under ``scopes``; None
    where no operation carries them (a program without the scopes)."""
    return _per_round_ms(
        run, lambda ev, lo, hi: scope_ns(ev["ops"], scopes, lo, hi))


def span_ms(run: Dict, name: str) -> Optional[float]:
    """Host milliseconds per traced round in the ``repro.<name>`` spans;
    None where the program has no such span."""
    return _per_round_ms(
        run, lambda ev, lo, hi: span_ns(ev["spans"], "repro." + name, lo,
                                        hi))


#: the named scopes of the round program and what each holds
SCOPES = {"local_steps": ("local_steps",), "mix": ("mix",),
          "page_device": ("page_in", "page_out")}


def summary(events: Dict[str, List]) -> Dict:
    """Per traced round (a ``bench.step_round`` span in the window):
    busy time, each scope's device time and each ``repro.*`` span's host
    time (ms); and the ten longest idle gaps, each named by the innermost
    host span of either kind that covers it."""
    lo, hi = window(events)
    rounds = sum(1 for n, s, _ in events["spans"]
                 if n == tr.SPAN + "step_round" and lo <= s <= hi) or 1
    dev = [[t, s, e] for t, s, e, _ in events["ops"]]
    out = {"rounds": rounds, "window_ms": 1e-6 * (hi - lo) / rounds,
           "busy_ms": 1e-6 * tr.busy_ns(dev, lo, hi) / rounds}
    for name, scopes in SCOPES.items():
        out[name + "_ms"] = 1e-6 * scope_ns(events["ops"], scopes, lo,
                                            hi) / rounds
    names = sorted({s[0] for s in events["spans"]
                    if s[0].startswith("repro.")})
    out["spans_ms"] = {n: 1e-6 * span_ns(events["spans"], n, lo, hi)
                       / rounds for n in names}
    out["idle_gaps"] = tr.idle_gaps(dev, events["spans"], lo, hi)
    return out


if __name__ == "__main__":
    import json
    path = newest_xplane(sys.argv[1])
    if path is None:
        raise SystemExit(f"no .xplane.pb at {sys.argv[1]}")
    print(json.dumps(summary(load(path)), indent=1))
