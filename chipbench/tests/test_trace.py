"""The reduction from a profiler trace to the per-layer numbers: by hand
on a made-up trace, and on small traces recorded on a TPU v5e."""

import glob
import os

import pytest

from chipbench.harness import spec, trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
NAMES = {"collectives": spec.names()["collectives"], "kernels": spec.names()["kernels"]}


def test_reduction_by_hand():
    ops = {0: [(0, 10, "fusion.1", ""), (5, 15, "all-reduce.2", ""),
               (12, 14, "fusion.3", ""), (40, 50, "custom-call.3", "... _wire_encode_kernel"),
               (60, 70, "custom-call.9", "... _wire_peel_kernel ..."),
               (80, 120, "fusion.1", "")],
           1: [(0, 50, "fusion.1", ""), (50, 60, "all-gather-start.1", "")]}
    spans = [(0, 100, "bench.window"), (14, 40, "bench.readback"),
             (52, 58, "bench.batch")]
    r = trace.reduce(ops, spans, NAMES, 2)
    # device 0 busy: [0,15] + [40,50] + [60,70] + [80,100] = 55; device 1: 60
    assert r["busy_s"] == pytest.approx(57.5e-9)
    assert r["window_s"] == pytest.approx(100e-9)
    # collectives: 10 on device 0, 10 on device 1
    assert r["collective_s"] == pytest.approx(10e-9)
    # exposed: device 0 [10,12] + [14,15] = 3, device 1 all 10
    assert r["exposed_collective_s"] == pytest.approx(6.5e-9)
    # kernels by instruction text, averaged over 2 devices
    assert r["kernel_s"] == {"producer": pytest.approx(5e-9), "consumer": pytest.approx(5e-9)}
    # device 0 gaps [15,40] readback, [50,60] batch, [70,80] none
    assert r["breakdown"]["idle_gaps"] == [
        ["bench.readback", pytest.approx(25e-9)], ["bench.batch", pytest.approx(10e-9)],
        ["none", pytest.approx(10e-9)]]
    # fusion.1: device 0 10 + 20 (clipped at the window), device 1 50
    assert r["breakdown"]["device_ops"][0] == ["fusion.1", pytest.approx(40e-9)]


def test_loops_count_by_their_innermost_events():
    evs = [(0, 100, "while.1", ""), (0, 10, "fusion.1", ""), (20, 30, "while.2", ""),
           (20, 25, "fusion.2", ""), (25, 30, "fusion.3", ""), (40, 50, "fusion.4", "")]
    assert [e[2] for e in trace.innermost(evs)] == ["fusion.1", "fusion.2", "fusion.3", "fusion.4"]
    text = "%all-reduce.7 = f32[8]{0:T(128)} all-reduce(f32[8]{0} %x)"
    assert trace.op_name(text) == "all-reduce.7"
    assert trace.op_label("all-reduce.7", text) == "all-reduce.7 f32[8]"


def test_unmatched_kernel_reads_nothing():
    ops = {0: [(0, 10, "fusion.1", "")]}
    r = trace.reduce(ops, [(0, 10, "bench.window")], NAMES, 1)
    assert r["kernel_s"] == {} and r["collective_s"] == 0


RECORDED = sorted(glob.glob(os.path.join(DATA, "*.xplane.pb")))


@pytest.mark.parametrize("path", RECORDED, ids=os.path.basename)
def test_recorded_chip_trace(path):
    ops, spans = trace.load(path)
    n = len(ops)
    r = trace.reduce(ops, spans, spec.names(), n)
    assert 0 < r["busy_s"] <= r["window_s"]
    assert 0 < r["device_span_s"] <= r["window_s"]
    assert r["breakdown"]["device_ops"]
    if n > 1:
        assert r["collective_s"] > 0
        assert 0 <= r["exposed_collective_s"] <= r["collective_s"] * n
        assert set(r["kernel_s"]) == {"producer", "consumer"}
