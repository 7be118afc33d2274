"""The serving engine's own spans and request stamps, and what the benchmark
reads from them: the spans' nesting and request ids in a profile recorded
on the CPU, the stamps' order on the window's clock, the device clock's
offset and the idle time by program span on a synthetic trace,
``program_spans.py`` over a recorded and a synthetic profile, and the two
readers that use them."""
import glob
import json
import subprocess
import sys
from types import SimpleNamespace

import bench_chip_helpers as helpers
import jax
import jax.numpy as jnp
import pytest

from benchmarks.chip import program_spans as ps
from benchmarks.chip import run as bench
from benchmarks.chip.trace import Device, Summary

MS = 1_000_000  # ns
SEED = 2**33 + 17


def _read(metric, run):
    return bench.load_module(bench.HERE / "metrics" / f"{metric}.py", f"m_{metric}").read(run)


# ------------------------------------------------------------ the program --
def _parent(spans, k):
    """The innermost span that holds span ``k`` (``collect``'s order puts
    an enclosing span first)."""
    _, s, e, _ = spans[k]
    for j in range(k - 1, -1, -1):
        if spans[j][1] <= s and e <= spans[j][2]:
            return spans[j]
    return None


def _tiny_engine(n_requests=3):
    from repro.configs import get_config
    from repro.models import transformer as tf
    from repro.runtime.serve import ServingEngine

    cfg = get_config("qwen3-0.6b").reduced()
    params = tf.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    eng = ServingEngine(cfg, params, batch_slots=2, max_len=32)
    prompts = {}
    for i in range(n_requests):  # more requests than slots: one waits a tick
        prompt = [1 + i, 2, 3][: 2 + i % 2]
        prompts[eng.submit(prompt, max_new_tokens=3)] = prompt
    return eng, prompts


def _profiled(out_dir, run):
    """Call ``run()`` under the profiler; its result and the trace's path."""
    jax.profiler.start_trace(str(out_dir))
    try:
        result = run()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(out_dir / "**" / "*.xplane.pb"), recursive=True)
    return result, path


@pytest.fixture(scope="module")
def engine_profile(tmp_path_factory):
    """A tiny engine drained under the profiler: the trace's directory,
    prompts by request id, and the finished requests."""
    eng, prompts = _tiny_engine()
    out_dir = tmp_path_factory.mktemp("engine_profile")
    done, _ = _profiled(out_dir, lambda: eng.run_until_drained(max_ticks=50))
    return out_dir, prompts, done


def _calls(prompts, done):
    """Decode calls per request: its prompt, then every output token but
    the first (the prompt's last logits give that one)."""
    return {r.rid: len(prompts[r.rid]) + r.max_new_tokens - 1 for r in done}


def test_engine_spans_nest_and_carry_request_ids(engine_profile):
    from jax.profiler import ProfileData

    out_dir, prompts, done = engine_profile
    (path,) = glob.glob(str(out_dir / "**" / "*.xplane.pb"), recursive=True)
    spans = ps.collect(ProfileData.from_file(path))

    assert {n for n, *_ in spans} == {"engine.step", "engine.admit", "engine.decode_call",
                                      "engine.writeback", "engine.sample"}
    for k, (name, _, _, rid) in enumerate(spans):
        parent = _parent(spans, k)
        if name == "engine.step":
            assert parent is None and rid is None
            continue
        assert rid in prompts, (name, rid)
        if name in ("engine.admit", "engine.sample"):
            assert parent[0] == "engine.step", name
        elif name == "engine.decode_call":
            assert parent[0] in ("engine.step", "engine.admit")
            assert parent[0] == "engine.step" or parent[3] == rid
        else:  # the write-back of its decode call's cache lane
            assert parent[0] == "engine.decode_call" and parent[3] == rid
    for req in done:
        count = {n: sum(1 for m, *_, r in spans if m == n and r == req.rid)
                 for n in ("engine.admit", "engine.decode_call", "engine.writeback",
                           "engine.sample")}
        calls = _calls(prompts, done)[req.rid]
        assert count == {"engine.admit": 1, "engine.decode_call": calls,
                         "engine.writeback": calls, "engine.sample": req.max_new_tokens}
        assert req.submitted_at <= req.admitted_at <= req.first_token_at <= req.finished_at


def test_request_stamps_are_ordered_on_the_window_clock():
    cell, driver = helpers.tiny_cell("tiny-qwen3", seed=SEED, seconds=2.0)
    state = driver.setup(cell)
    rec = driver.window(cell, state, bench.Clock(cell.seconds, None))
    answered = [tr for tr in rec["requests"] if tr.req.done]
    assert answered
    for tr in answered:
        r = tr.req
        assert tr.due <= r.submitted_at <= r.admitted_at <= r.first_token_at <= r.finished_at
        # the driver stamps a token at the end of the tick that read it back
        assert r.first_token_at <= tr.tokens[0] and r.finished_at <= tr.tokens[-1]
        if tr.admitted is not None and tr.admitted <= rec["t_close"]:
            assert tr.admitted <= r.admitted_at  # the admitting tick's start
    assert any(rec["t0"] <= tr.req.admitted_at <= rec["t_close"] for tr in answered)


def test_cli_reads_an_engine_profile(engine_profile):
    # a profile taken outside the benchmark: no bench.window, no TPU; the
    # window is the ticks' extent and only the host's side is printed
    out_dir, prompts, done = engine_profile
    script = bench.ROOT / "benchmarks" / "chip" / "program_spans.py"
    proc = subprocess.run([sys.executable, str(script), str(out_dir)], capture_output=True,
                          text=True, cwd=bench.ROOT, timeout=120, check=True)
    out = json.loads(proc.stdout)
    count = {name: c for name, (c, _) in out["spans"].items()}
    calls = sum(_calls(prompts, done).values())
    assert count.pop("engine.step") >= 3  # the last request waits a tick for a slot
    assert count == {"engine.admit": len(done), "engine.decode_call": calls,
                     "engine.writeback": calls,
                     "engine.sample": sum(r.max_new_tokens for r in done)}
    step_s = out["spans"]["engine.step"][1]
    assert 0 < step_s <= out["window_s"]
    assert "clock_offset_ns" not in out and "idle_by_program_span" not in out


def test_cli_reads_the_benchmark_window(tmp_path):
    # with a bench.window span, only what overlaps it is read
    from jax.profiler import ProfileData

    eng, _ = _tiny_engine()
    eng.step()  # compiles, before the window

    def run():
        with jax.profiler.TraceAnnotation("bench.window"):
            eng.step()
            eng.step()
        return eng.run_until_drained(max_ticks=50)

    _, path = _profiled(tmp_path, run)
    pd = ProfileData.from_file(path)
    ticks = [(s, e) for n, s, e, _ in ps.collect(pd) if n == "engine.step"]
    window = ps.window_of(pd)
    assert len(ticks) > 2 and window[0] <= ticks[0][0] and ticks[1][1] <= window[1] < ticks[2][0]
    out = ps.read(pd)
    assert out["window_s"] == pytest.approx((window[1] - window[0]) * 1e-9)
    assert out["spans"]["engine.step"][0] == 2
    with pytest.raises(ValueError):
        ps.read(SimpleNamespace(planes=[]))


# ------------------------------------------------------- synthetic traces --
N = 30  # ticks, 10 ms apart, the first at 5 ms; the window 0 .. 10 N + 5 ms
LAT = [5_000 * (1 + k % 3) for k in range(N)]  # read-back ends after the device, ns


def _tick(k):
    return 5 * MS + 10 * MS * k


def _synthetic(lag_ns, ticks_as="engine", blip=False):
    """Device work of tick k, host-time 0.35 .. 6 ms into it and written on
    the device's clock (host time + ``lag_ns``): the decode program, then
    the read-back's argmax 5.95 .. 6. Host: ``engine.step`` 0 .. 8,
    ``engine.decode_call`` 0.1 .. 0.3 holding ``engine.writeback`` 0.2 ..
    0.3, ``engine.sample`` 0.3 .. 6 ms plus the read-back's latency. With
    ``ticks_as="bench"``: only the driver's ``bench.step``, ending at the
    read-back (0 .. 6 ms plus 0 or 20 us). With ``blip``: one more short op
    in tick 7, 6.5 .. 6.6 ms, after its read-back."""
    def dev(k, a, b):
        return _tick(k) + a + lag_ns, _tick(k) + b + lag_ns

    ops = [("%fusion.1", *dev(k, 350_000, 5_950_000)) for k in range(N)]
    ops += [("%argmax.1", *dev(k, 5_950_000, 6 * MS)) for k in range(N)]
    modules = [(f"jit__lambda({k})", *dev(k, 350_000, 5_950_000)) for k in range(N)]
    modules += [(f"jit__argmax({k})", *dev(k, 5_950_000, 6 * MS)) for k in range(N)]
    if blip:
        ops.append(("%copy.1", *dev(7, 6_500_000, 6_600_000)))
    window = (0, 10 * MS * N + 5 * MS)
    summary = Summary(window=window, spans=[("bench.window", *window)],
                      devices=[Device("/device:TPU:0", ops=ops, modules=modules)])
    if ticks_as == "bench":
        summary.spans += [("bench.step", _tick(k), _tick(k) + 6 * MS + 20_000 * (k % 2))
                          for k in range(N)]
        return summary, []
    spans = []
    for k in range(N):
        t = _tick(k)
        spans += [("engine.step", t, t + 8 * MS, None),
                  ("engine.decode_call", t + 100_000, t + 300_000, k),
                  ("engine.writeback", t + 200_000, t + 300_000, k),
                  ("engine.sample", t + 300_000, t + 6 * MS + LAT[k], k)]
    return summary, spans


def _samples(spans):
    return [(s, e) for n, s, e, _ in spans if n == "engine.sample"]


def _placed(items, containers, offset):
    """How many ``items`` lie inside a container moved by ``offset``."""
    return sum(any(s + offset <= a and b <= e + offset for s, e in containers)
               for a, b in items)


def _profile(summary, spans):
    """``summary`` and host ``spans`` as the ``ProfileData`` of a one-chip
    trace: the host's events with their ``rid`` stats, chip 0's ops and
    programs."""
    def events(items):
        return [SimpleNamespace(name=n, start_ns=s, duration_ns=e - s,
                                stats=[] if rest in ([], [None]) else [("rid", rest[0])])
                for n, s, e, *rest in items]

    dev = summary.devices[0]
    host = [SimpleNamespace(name="python", events=events(summary.spans + spans))]
    chip = [SimpleNamespace(name="XLA Ops", events=events(dev.ops)),
            SimpleNamespace(name="XLA Modules", events=events(dev.modules))]
    return SimpleNamespace(planes=[SimpleNamespace(name="/host:CPU", lines=host),
                                   SimpleNamespace(name=dev.name, lines=chip)])


@pytest.mark.parametrize("lag_ns", [-MS, 700_000, 0])
def test_clock_offset_is_recovered_from_the_read_backs(lag_ns):
    summary, spans = _synthetic(lag_ns)
    items = ps.read_backs(summary)
    assert len(items) == N
    got = ps.clock_offset_ns(items, _samples(spans))
    assert abs(got - lag_ns) <= 20_000, got
    assert _placed(items, _samples(spans), got) == N
    assert ps.clock_offset_ns(items[:ps.MIN_ITEMS - 1], _samples(spans)) == 0


def test_clock_offset_ignores_a_short_op_after_a_read_back():
    # the op cuts the device's idle gap after one read-back in two, so the
    # read-backs' ends also fit a placement about 0.6 ms nearer 0
    summary, spans = _synthetic(-MS, blip=True)
    got = ps.clock_offset_ns(ps.read_backs(summary), _samples(spans))
    assert abs(got + MS) <= 20_000


def test_idle_by_program_span_names_each_gap():
    summary, spans = _synthetic(-MS)
    gaps = summary.gaps(0)
    by_span = ps.idle_by_span(ps.shift(spans, -MS, summary.window), gaps, summary.window)
    lat = sum(LAT) * 1e-9
    want = {"engine.step": N * 0.1e-3 + N * 2e-3 - lat,  # before the first call; after the read
            "engine.decode_call": N * 0.1e-3, "engine.writeback": N * 0.1e-3,
            "engine.sample": N * 0.05e-3 + lat,  # until the device starts; the read-back
            "outside": N * 2e-3 + 5e-3}  # between the ticks, and before the first, after the last
    assert by_span == pytest.approx(want, rel=1e-9)
    idle = (summary.window_s - summary.busy_s())
    assert sum(by_span.values()) == pytest.approx(idle, rel=1e-9)

    red = ps.read(_profile(summary, spans))  # the CLI's reduction of a TPU profile
    assert abs(red["clock_offset_ns"] + MS) <= 20_000
    assert red["read_backs"] == N
    assert dict(red["idle_by_program_span"]) == pytest.approx(want, abs=N * 6e-6)
    assert sum(t for _, t in red["idle_by_program_span"]) == pytest.approx(idle, rel=1e-9)
    assert red["idle_s"] == pytest.approx(idle)
    assert red["spans"]["engine.step"] == [N, pytest.approx(N * 8e-3)]
    # each tick: 0.35 ms before the device starts, 2 ms after its read-back
    assert red["tick_idle_share"] == pytest.approx(2.35 / 8, abs=1e-3)
    # without the offset the device's work seems to start before the call
    unshifted = ps.idle_by_span(spans, gaps, summary.window)
    assert unshifted.get("engine.decode_call", 0.0) == 0.0


# ----------------------------------------------------------------- readers --
def _stamped(submitted, admitted, first, prompt_len):
    req = SimpleNamespace(submitted_at=submitted, admitted_at=admitted,
                          first_token_at=first, prompt=[1] * prompt_len)
    return SimpleNamespace(req=req)


def _stamp_run(requests):
    return SimpleNamespace(rec={"t0": 10.0, "t_close": 20.0, "requests": requests})


STAMPED = [_stamped(10.1, 10.3, 10.5, 10),  # waits 0.2 s, 20 ms a prompt token
           _stamped(11.0, 11.5, 11.6, 4),  # 0.5 s, 25 ms
           _stamped(12.0, 13.0, 14.9, 100),  # 1.0 s, 19 ms
           _stamped(9.0, 9.5, 9.6, 4),  # admitted before the window
           _stamped(19.0, 19.9, None, 8)]  # 0.9 s; no first token in the window


def test_stamp_readers():
    run = _stamp_run(STAMPED)
    assert _read("engine.prefill_ms_per_token_p50", run) == pytest.approx(20.0)


@pytest.mark.parametrize("metric", ["engine.prefill_ms_per_token_p50"])
def test_stamp_readers_return_nothing_without_stamps(metric):
    unstamped = [SimpleNamespace(req=SimpleNamespace(submitted_at=10.5, prompt=[1]))]
    assert _read(metric, _stamp_run(unstamped)) is None  # an engine without the stamps
    assert _read(metric, _stamp_run([_stamped(10.1, None, None, 3)])) is None  # queued
    assert _read(metric, _stamp_run([])) is None


def test_host_gap_share_reader():
    summary, _ = _synthetic(-MS, ticks_as="bench")
    run = SimpleNamespace(trace=summary)
    # each tick: idle 0.35 ms before the device starts, and its read-back
    # (0 or 20 us), on the device's clock
    extra = [20_000 * (k % 2) for k in range(N)]
    want = 100 * sum(350_000 + x for x in extra) / sum(6 * MS + x for x in extra)
    assert _read("engine.host_gap_share", run) == pytest.approx(want, rel=1e-9)
    summary.spans = [s for s in summary.spans if s[0] != "bench.step"]
    assert _read("engine.host_gap_share", run) is None
