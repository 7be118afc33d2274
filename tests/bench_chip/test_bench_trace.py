"""The reduction from a profiler trace to busy time, idle share, op and
program time and host-attributed gaps."""
import gzip
from pathlib import Path

import pytest

from benchmarks.chip import trace
from benchmarks.chip.metrics import _common
from benchmarks.chip.trace import Device, Summary


def _summary():
    # window 0..100 ns on two devices; host spans: a step 0..65, a wait 65..100
    d0 = Device("/device:TPU:0", ops=[("fusion.1", 0, 30), ("fusion.2", 20, 40),
                                      ("copy.1", 35, 50), ("copy.1", 70, 80)],
                modules=[("jit__lambda", 0, 50), ("jit_argmax", 70, 80)])
    d1 = Device("/device:TPU:1", ops=[("fusion.1", 10, 90)], modules=[("jit__lambda", 10, 90)])
    spans = [("bench.window", 0, 100), ("bench.step", 0, 65), ("bench.wait", 65, 100)]
    return Summary(window=(0, 100), devices=[d0, d1], spans=spans)


def test_union_and_subtract():
    assert trace._union([(5, 7), (0, 3), (2, 4), (7, 9)]) == [(0, 4), (5, 9)]
    assert trace._subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert trace._subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]


def test_busy_idle_and_times():
    s = _summary()
    # device 0 busy 0..50 and 70..80 = 60 ns; device 1 busy 80 ns
    assert s.busy_s() == pytest.approx(70e-9)
    assert s.idle_share() == pytest.approx(0.3)
    assert s.window_s == pytest.approx(100e-9)
    assert s.op_seconds(lambda n: n == "fusion.1") == pytest.approx((30 + 80) / 2 * 1e-9)
    assert s.op_count(lambda n: n.startswith("fusion")) == 3
    assert s.module_seconds(lambda n: n == "jit__lambda") == pytest.approx(65e-9)


def test_breakdown_names_gaps_by_open_host_span():
    b = _summary().breakdown()
    assert b["device_ops"][0][0] == "fusion.1"
    # device 0 idles 50..70 (mid-gap inside the step) and 80..100 (the wait)
    assert sorted(b["idle_gaps"]) == [["bench.step", pytest.approx(20e-9)],
                                      ["bench.wait", pytest.approx(20e-9)]]
    assert len(b["device_ops"]) <= trace.TOP and len(b["idle_gaps"]) <= trace.TOP


# --------------------------------------------------------- recorded trace --
# tests/bench_chip/record_trace.py on one v5e chip: three rounds of a jitted
# matmul step, the Pallas flash kernel (1 x 512 tokens, 16/8 heads of 128)
# and a wait, each in its host span, all inside "bench.window"; gzipped, with
# the checkout's path in the source locations replaced by "/work/repo/" (same
# length, so the protobuf stays valid). The device's clock in this trace
# runs about 1 ms behind the host's, so the first round's device work falls
# before the window opens.
RECORDED = Path(__file__).with_name("data") / "v5e-1chip.xplane.pb.gz"


def _is_flash_op(name):
    """The Pallas kernel's custom call: an op's name is its HLO text, the
    instruction's own name the part before " = "."""
    return "flash_attention" in name.split(" = ", 1)[0] and "custom-call" in name


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData

    pd = ProfileData.from_serialized_xspace(gzip.decompress(RECORDED.read_bytes()))
    return trace.summarize_data(pd)


def test_recorded_trace_planes_and_window(recorded):
    assert [d.name for d in recorded.devices] == ["/device:TPU:0"]
    assert recorded.window_s == pytest.approx(0.0497, rel=0.01)
    assert [n for n, _, _ in recorded.spans].count("bench.flash") == 3
    assert 0 < recorded.busy_s() < recorded.window_s
    assert recorded.idle_share() == pytest.approx(0.9929, abs=1e-3)


def test_recorded_trace_finds_the_flash_kernel_and_programs(recorded):
    assert recorded.op_count(_is_flash_op) == 2
    per_call = recorded.op_seconds(_is_flash_op) / 2
    assert per_call == pytest.approx(145.092e-6, rel=1e-6)
    assert recorded.module_seconds(_common.is_decode_module) > 0  # jit__lambda
    b = recorded.breakdown()
    assert _is_flash_op(b["device_ops"][0][0])
    assert b["idle_gaps"][0][0] == "bench.wait"
    assert {name for name, _ in b["idle_gaps"]} <= {"bench.wait", "bench.flash", "bench.step",
                                                    "none"}
