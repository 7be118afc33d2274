"""Each per-layer metric reader, on a synthetic traced run: a number where
its layer left something to read, nothing where it did not, and no share
of a roofline or a peak above 100%."""
import json
from types import SimpleNamespace

import pytest

from benchmarks.chip import run as bench
from benchmarks.chip import work
from benchmarks.chip.trace import Device, Summary

PEAKS = work.peaks("TPU v5 lite")
MS = 1_000_000  # ns


def _cell(config, traffic, chips):
    config = json.loads((bench.HERE / "configs" / f"{config}.json").read_text())
    traffic = json.loads((bench.HERE / "traffic" / f"{traffic}.json").read_text())
    return SimpleNamespace(model=bench.model_numbers(config), traffic=traffic,
                           config=config, peaks=PEAKS, chips=chips)


def _read(metric, run):
    return bench.load_module(bench.HERE / "metrics" / f"{metric}.py", f"m_{metric}").read(run)


def _serve_run(decode_ms_per_call=14.2):
    cell = _cell("qwen3-0.6b", "chat", 1)
    m = cell.model
    # 100 ticks of one decode call at position 200, each 20 ms, in a 2 s window
    f, b = work.decode_call(m, 200)
    ticks = [(0.02 * i, 0.02 * i + 0.02, f, b, 1) for i in range(100)]
    dev = Device("/device:TPU:0",
                 ops=[("%fusion.1 = bf16[8]", i * 20 * MS, i * 20 * MS + 17 * MS)
                      for i in range(100)],
                 modules=[("jit__lambda(123)", i * 20 * MS, i * 20 * MS + int(decode_ms_per_call * MS))
                          for i in range(100)])
    tr = Summary(window=(0, 2000 * MS), devices=[dev], spans=[("bench.window", 0, 2000 * MS)])
    req = SimpleNamespace(admitted=0.5, due=0.3, tokens=[0.6])
    rec = {"ticks": ticks, "requests": [req], "t0": 0.0, "t_close": 2.0}
    return SimpleNamespace(cell=cell, rec=rec, trace=tr,
                           clock=SimpleNamespace(trace_t0=0.0, trace_t1=2.0))


def test_serving_readers():
    run = _serve_run()
    assert _read("engine.queue_wait_p50_ms", run) == pytest.approx(200.0)
    assert _read("engine.tick_p50_ms", run) == pytest.approx(20.0)
    need = work.roofline_seconds(*work.decode_call(run.cell.model, 200), PEAKS)[0]
    assert _read("decode_step_roofline", run) == pytest.approx(100 * need / 14.2e-3)
    assert _read("device.idle_share.serve", run) == pytest.approx(15.0)
    flops = 100 * work.decode_call(run.cell.model, 200)[0]
    assert _read("serve.mfu", run) == pytest.approx(100 * flops / (1.7 * PEAKS["bf16_flops_per_s"]))


@pytest.mark.parametrize("metric", ["decode_step_roofline"])
def test_readers_return_nothing_without_their_ops(metric):
    run = _serve_run()
    empty = Summary(window=run.trace.window,
                    devices=[Device(d.name, ops=[("%fusion.1 = f32[]", 0, MS)])
                             for d in run.trace.devices],
                    spans=run.trace.spans)
    run.trace = empty
    assert _read(metric, run) is None


def test_shares_of_a_roofline_or_peak_stay_under_100():
    run = _serve_run(decode_ms_per_call=1.5)
    for metric in ("decode_step_roofline", "serve.mfu"):
        assert 0 < _read(metric, run) <= 100, metric
