"""The program's names in a trace (``bench/scopes.py``): device time
under named scopes, ``repro.*`` host spans, gaps named by either kind of
span, and the readers of the metrics that use them."""
import glob
import gzip
import json
import os

import pytest

from bench import harness, scopes
from bench import trace as tr
from bench.metrics import (assemble_ms, cold_codec_roofline,
                           gossip_mix_roofline, local_steps_ms, mix_ms,
                           page_device_ms, page_wait_ms)

HERE = os.path.dirname(os.path.abspath(__file__))

READERS = [local_steps_ms, mix_ms, page_device_ms, page_wait_ms,
           assemble_ms]

#: hand-made operations: [text, start, end, name stack]
OPS = [
    ["%while.1 = (f32[2]) while(%t), body=%b", 0, 100,
     "jit(r)/local_steps/while"],
    ["%f.1 = f32[2] fusion(%x)", 10, 30,
     "jit(r)/while/body/local_steps/while/body/mul"],
    ["%f.2 = f32[2] fusion(%x)", 20, 40,
     "jit(r)/while/body/local_steps/while/body/add"],
    ["%pad.1 = f32[2] pad(%y, %c)", 45, 50, "jit(r)/while/body/mix/pad"],
    ["%gossip_mix.1 = f32[2] custom-call(%w, %pad.1)", 50, 60,
     "jit(r)/while/body/mix/gossip_mix/pallas_call"],
    ["%slice.1 = f32[2] slice(%gossip_mix.1)", 58, 70,
     "jit(r)/while/body/mix/slice"],
    ["%f.3 = f32[2] fusion(%x)", 72, 75, "jit(r)/mixer/add"],
    ["%copy.1 = f32[2] copy(%x)", 80, 90, ""],
    ["%cold_dequantize.1 = f32[2] custom-call(%q)", 110, 130,
     "jit(pre)/page_in/cold_dequantize/pallas_call"],
    ["%f.4 = f32[2] fusion(%q)", 140, 150, "jit(post)/page_out/where"],
]


def test_scope_time_is_a_union_without_loops():
    assert scopes.scope_ns(OPS, ("local_steps",), 0, 200) == 30
    assert scopes.scope_ns(OPS, ("mix",), 0, 200) == 25   # not "mixer"
    assert scopes.scope_ns(OPS, ("page_in", "page_out"), 0, 200) == 30
    assert scopes.scope_ns(OPS, ("local_steps",), 25, 35) == 10
    assert scopes.scope_ns(OPS, ("codec",), 0, 200) == 0


def test_gaps_are_named_by_the_innermost_span_of_either_kind():
    device = [[t, s, e] for t, s, e, _ in OPS[1:]]
    spans = [["bench.window", 0, 200], ["bench.step_round", 0, 160],
             ["repro.round", 0, 155], ["repro.page.wait", 90, 108],
             ["repro.assemble", 128, 142], ["bench.finish", 160, 200]]
    gaps = tr.idle_gaps(device, spans, 0, 200, k=10)
    assert gaps == [["bench.finish", pytest.approx(50e-9)],
                    ["repro.page.wait", pytest.approx(20e-9)],
                    ["repro.round", pytest.approx(10e-9)],
                    ["repro.assemble", pytest.approx(10e-9)],
                    ["repro.round", pytest.approx(5e-9)],
                    ["repro.round", pytest.approx(5e-9)],
                    ["repro.round", pytest.approx(2e-9)]]


def _xplane(path, ops, spans):
    """An ``.xplane.pb`` with a TPU plane of ``ops`` (name stacks in the
    operations' metadata) and a host plane of ``spans``."""
    space = scopes._schema()()
    for pname, lname, events in (("/device:TPU:0", "XLA Ops", ops),
                                  ("/host:CPU", "python3", spans)):
        plane = space.planes.add(id=1, name=pname.encode())
        sm = plane.stat_metadata.add(key=1)
        sm.value.id, sm.value.name = 1, b"tf_op"
        line = plane.lines.add(id=1, name=lname.encode(), timestamp_ns=0)
        for i, ev in enumerate(events, start=1):
            em = plane.event_metadata.add(key=i)
            em.value.id, em.value.name = i, ev[0].encode()
            if len(ev) > 3 and ev[3]:
                em.value.stats.add(metadata_id=1, str_value=ev[3].encode())
            line.events.add(metadata_id=i, offset_ps=int(ev[1] * 1000),
                            duration_ps=int((ev[2] - ev[1]) * 1000))
    with open(path, "wb") as f:
        f.write(space.SerializeToString())


SPANS = [["bench.window", 0, 200], ["bench.step_round", 0, 100],
         ["repro.round", 0, 100], ["repro.assemble", 0, 4],
         ["repro.page.wait", 60, 80], ["bench.step_round", 100, 200],
         ["repro.round", 100, 200], ["repro.assemble", 100, 108],
         ["repro.page.wait", 150, 160], ["other.span", 0, 200]]


def _run(cell, rounds=2, peaks=True):
    return {"cell": cell, "trace": {"rounds": rounds},
            "peaks": {"hbm_bytes_per_s": 1.0} if peaks else None}


@pytest.fixture
def out_dir(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "OUT", str(tmp_path))
    return tmp_path


def test_load_keeps_ops_with_their_stacks_and_the_named_spans(out_dir):
    path = out_dir / "a.xplane.pb"
    _xplane(path, OPS, SPANS)
    ev = scopes.load(str(path))
    assert ev["ops"] == [[t, float(s), float(e), st] for t, s, e, st in OPS]
    assert sorted(map(tuple, ev["spans"])) == sorted(
        (n, float(s), float(e)) for n, s, e in SPANS if n != "other.span")


def test_readers_read_per_traced_round(out_dir):
    (out_dir / "trace" / "cell" / "p").mkdir(parents=True)
    _xplane(out_dir / "trace" / "cell" / "p" / "x.xplane.pb", OPS, SPANS)
    run = _run("cell")
    assert local_steps_ms.read(run) == pytest.approx(30e-6 / 2)
    assert mix_ms.read(run) == pytest.approx(25e-6 / 2)
    assert page_device_ms.read(run) == pytest.approx(30e-6 / 2)
    assert assemble_ms.read(run) == pytest.approx(12e-6 / 2)
    assert page_wait_ms.read(run) == pytest.approx(30e-6 / 2)
    # the script's summary of the same trace: two step_round spans
    sm = scopes.summary(run["trace"]["scopes"])
    assert sm["rounds"] == 2
    assert sm["local_steps_ms"] == pytest.approx(local_steps_ms.read(run))
    assert sm["spans_ms"]["repro.page.wait"] == pytest.approx(30e-6 / 2)
    assert sm["idle_gaps"][0] == ["repro.round", pytest.approx(50e-9)]


@pytest.mark.parametrize("reader", READERS, ids=lambda r: r.__name__)
def test_readers_find_nothing_without_scopes_or_spans(out_dir, reader):
    """The parent's trace: no operation carries a scope, no repro span."""
    d = out_dir / "trace" / "cell" / "p"
    d.mkdir(parents=True)
    _xplane(d / "x.xplane.pb", [o[:3] for o in OPS],
            [s for s in SPANS if not s[0].startswith("repro.")])
    assert reader.read(_run("cell")) is None
    # nor on a run that traced nothing, or without a chip's peaks
    assert reader.read({"cell": "cell", "trace": None,
                        "peaks": {}}) is None
    _xplane(d / "x.xplane.pb", OPS, SPANS)
    assert reader.read(_run("cell", peaks=False)) is None
    assert reader.read(_run("none")) is None


def test_load_agrees_with_the_harness_on_a_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: x * 2)
    f(jnp.ones(4)).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.window"):
            with jax.profiler.TraceAnnotation("repro.round"):
                f(jnp.ones(4)).block_until_ready()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    ev = scopes.load(path[0])
    assert ev["ops"] == []                    # no TPU here
    assert [s[0] for s in ev["spans"]] == ["bench.window", "repro.round"]
    assert ev["spans"][:1] == tr.load(path[0])["spans"]   # the same clock
    assert scopes.window(ev) == tuple(ev["spans"][0][1:])



@pytest.fixture(scope="module")
def scoped():
    """One pipelined round of the population cell on a TPU v5 lite, with
    the program's scopes and spans (``xdevice_scoped_round.json.gz``)."""
    with gzip.open(os.path.join(HERE, "xdevice_scoped_round.json.gz")) as f:
        return json.load(f)


def test_recorded_round_time_by_scope(scoped):
    ops, (lo, hi) = scoped["ops"], scopes.window(scoped)
    got = {k: scopes.scope_ns(ops, v, lo, hi)
           for k, v in scopes.SCOPES.items()}
    assert got == {"local_steps": 3_339_183_206, "mix": 142_855_975,
                   "page_device": 134_941_802}
    busy = tr.busy_ns([o[:3] for o in ops], lo, hi)
    assert sum(got.values()) <= busy == 3_711_439_342
    # the scopes are disjoint: their union is their sum
    assert scopes.scope_ns(ops, ("local_steps", "mix", "page_in",
                                 "page_out"), lo, hi) == sum(got.values())


def test_recorded_kernels_sit_in_their_scopes_by_name(scoped):
    mix = [o for o in scoped["ops"] if gossip_mix_roofline.is_mix(o[0])]
    codec = [o for o in scoped["ops"]
             if cold_codec_roofline.is_codec(o[0])]
    assert len(mix) == 8 and len(codec) == 24
    assert all(o[0].startswith("%gossip_mix.") for o in mix)
    assert {o[0].split(".")[0] for o in codec} == {
        "%cold_absmax", "%cold_quantize", "%cold_dequantize"}
    assert all(scopes.in_scope(o[3], ("mix",)) for o in mix)
    assert all(scopes.in_scope(o[3], ("page_in", "page_out"))
               for o in codec)
    # the kernel is a third of the mixing scope: pad and slice copies
    mix_ns = sum(e - s for _, s, e, _ in mix)
    assert mix_ns == 50_329_821 < scopes.scope_ns(
        scoped["ops"], ("mix",), *scopes.window(scoped)) / 2


def test_recorded_round_enters_each_span_once(scoped):
    names = [s[0] for s in scoped["spans"] if s[0].startswith("repro.")]
    assert sorted(names) == sorted(
        "repro." + n for n in ("round", "assemble", "dispatch", "page.wait",
                               "page.commit", "page.stage"))
    sm = scopes.summary(scoped)
    parts = sum(v for k, v in sm["spans_ms"].items() if k != "repro.round")
    assert parts <= sm["spans_ms"]["repro.round"] <= sm["window_ms"]
    # every idle gap inside the round carries the program's span
    assert sm["idle_gaps"][0][0] == "repro.page.stage"
    assert all(g[0].startswith("repro.") for g in sm["idle_gaps"])
