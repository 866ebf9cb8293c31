"""The reduction from trace events to the per-layer metrics, on a small
recorded trace (one pipelined round of the population cell on a TPU v5
lite) and on hand-made events."""
import gzip
import json
import os

import numpy as np
import pytest

from bench import trace as tr
from bench.metrics import cold_codec_roofline, device_idle_share
from bench.metrics import gossip_mix_roofline

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(os.path.join(HERE, "xdevice_round_trace.json.gz")) as f:
        return json.load(f)


def test_union_merges_overlapping_and_touching_intervals():
    assert tr.union([(5, 7), (0, 2), (1, 3), (3, 4), (9, 10)]) == [
        (0, 4), (5, 7), (9, 10)]
    assert tr.union([]) == []


def test_busy_counts_nested_and_overlapping_ops_once():
    device = [["%while.1 = while()", 0, 100], ["%f = fusion()", 10, 20],
              ["%g = fusion()", 150, 170], ["%h = copy()", 160, 180]]
    assert tr.busy_ns(device, 0, 200) == 130
    assert tr.busy_ns(device, 50, 165) == 65   # clipped to the window


def test_recorded_busy_matches_a_timeline(recorded):
    red = tr.reduce(recorded, recorded["rounds"])
    lo, hi = recorded["spans"][0][1], recorded["spans"][0][2]
    # an independent count: mark every microsecond some op covers
    timeline = np.zeros(int((hi - lo) // 1000) + 1, bool)
    for _, s, e in recorded["device"]:
        timeline[int((max(s, lo) - lo) // 1000):
                 int((min(e, hi) - lo) // 1000)] = True
    assert red["busy_s"] == pytest.approx(timeline.sum() * 1e-6, rel=2e-2)
    assert red["window_s"] == pytest.approx((hi - lo) * 1e-9)
    share = device_idle_share.read({"trace": red})
    assert share == pytest.approx(100 * (1 - red["busy_s"]
                                         / red["window_s"]))
    assert 50 < share < 95      # the paging round leaves the chip idle


def test_kernel_time_sums(recorded):
    device = recorded["device"]
    mix = [d for d in device if gossip_mix_roofline.is_mix(d[0])]
    codec = [d for d in device if cold_codec_roofline.is_codec(d[0])]
    assert len(mix) == 1 and mix[0][0].startswith("%streamed_round.1 =")
    assert len(codec) == 24     # absmax, quantise, dequantise per leaf
    assert not set(map(id, mix)) & set(map(id, codec))
    mix_ns = sum(e - s for _, s, e in mix)
    codec_ns = sum(e - s for _, s, e in codec)
    assert mix_ns == 5_520_157
    assert codec_ns == 80_714_544
    run = {"trace": dict(tr.reduce(recorded, 1), counts={
               "mix_passes": 1, "mix_bytes": 4_000_000_000,
               "mix_flops": 1, "codec_bytes": 4_000_000_000}),
           "peaks": {"hbm_bytes_per_s": 1e12, "bf16_flops_per_s": 1e15}}
    assert gossip_mix_roofline.read(run) == pytest.approx(
        100 * 4e-3 / (mix_ns * 1e-9))
    assert cold_codec_roofline.read(run) == pytest.approx(
        100 * 4e-3 / (codec_ns * 1e-9))


def test_readers_find_nothing_without_kernels():
    run = {"trace": {"device": [["%f = fusion()", 0, 5]], "rounds": 1,
                     "busy_s": 5e-9, "window_s": 1e-8,
                     "counts": {"mix_passes": 1, "mix_bytes": 1,
                                "mix_flops": 1, "codec_bytes": 1}},
           "peaks": {"hbm_bytes_per_s": 1e12, "bf16_flops_per_s": 1e15}}
    assert gossip_mix_roofline.read(run) is None
    assert cold_codec_roofline.read(run) is None
    assert device_idle_share.read(
        {"trace": dict(run["trace"], device=[])}) is None


def test_gaps_are_named_by_the_innermost_host_span():
    device = [["%a = fusion()", 0, 10], ["%b = fusion()", 40, 50],
              ["%c = fusion()", 55, 60]]
    spans = [["bench.window", 0, 100], ["bench.step_round", 0, 30],
             ["bench.finish", 30, 100]]
    gaps = tr.idle_gaps(device, spans, 0, 100, k=10)
    assert gaps == [["bench.finish", pytest.approx(40e-9)],
                    ["bench.step_round", pytest.approx(30e-9)],
                    ["bench.finish", pytest.approx(5e-9)]]
    assert tr.idle_gaps(device, [], 0, 100, k=1) == [
        ["outside bench spans", pytest.approx(40e-9)]]


def test_recorded_breakdown(recorded):
    red = tr.reduce(recorded, 1)
    ops = red["breakdown"]["device_ops"]
    assert len(ops) == 10 and ops == sorted(ops, key=lambda o: -o[1])
    assert not any(o[0].endswith(" while") for o in ops)
    gaps = red["breakdown"]["idle_gaps"]
    assert gaps[0][0] == "bench.step_round"
    assert gaps[0][1] > 0.5      # the host pages for most of the round


def test_top_ops_group_by_instruction_and_skip_loops():
    device = [["%f.1 = f32[2] fusion(%x)", 0, 3],
              ["%f.1 = f32[2] fusion(%x)", 5, 9],
              ["%w = (s32[]) while(%t), body=%b", 0, 20],
              ["%c = f32[2] copy(%y)", 10, 15]]
    assert tr.top_ops(device) == [["%f.1 fusion", pytest.approx(7e-9)],
                                  ["%c copy", pytest.approx(5e-9)]]


def test_load_keeps_the_bench_spans_of_an_xplane(tmp_path):
    import glob

    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: x * 2)
    f(jnp.ones(4)).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.window"):
            with jax.profiler.TraceAnnotation("bench.step_round"):
                f(jnp.ones(4)).block_until_ready()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = tr.load(path[0])
    names = [s[0] for s in events["spans"]]
    assert names == ["bench.window", "bench.step_round"]
    win, step = events["spans"]
    assert win[1] <= step[1] <= step[2] <= win[2]
    red = tr.reduce(events, 1)
    assert red["window_s"] > 0 and red["busy_s"] == 0   # no TPU here
