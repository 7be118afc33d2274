"""The serving cell's end-to-end metrics count one whole schedule block: the
requests due in the window, every token of theirs whenever it is read back
(the drain after the close included), and nothing of the lead-in's. Where
the window's edges fall among the stamps moves none of them."""
import json
from types import SimpleNamespace

import pytest

import bench_chip_helpers as helpers
from benchmarks.chip import run as bench
from benchmarks.chip.traffic import open_loop

SERVE = bench.load_module(bench.HERE / "drivers" / "serve.py", "benchmarks.chip.drivers.serve")
E2E = ("output_tokens_per_s", "ttft_p95_ms", "itl_p95_ms")
T0 = 100.0


def _tr(due, stamps, done=True):
    return SimpleNamespace(req=SimpleNamespace(done=done), due=due, admitted=None,
                           tokens=list(stamps), prompt_len=8)


def _rec(t_close=T0 + 10.0, lead_shift=0.0):
    """A lead-in request open across the window's open, one request inside
    it, and one due just before the close that is read back only after it,
    with the longest gaps stamped there."""
    lead = _tr(T0 - 5.0, [T0 - 4.0 + lead_shift + 0.5 * k for k in range(12)])
    inner = _tr(T0 + 1.0, [T0 + 2.0 + 0.1 * k for k in range(20)])
    late = _tr(T0 + 9.0, [T0 + 9.5, T0 + 10.5, T0 + 12.5, T0 + 14.5, T0 + 16.5])
    return {"t0": T0, "t_close": t_close, "requests": [lead, inner, late], "ticks": [],
            "lateness": [0.0, 0.0, 0.0], "scheduled": 3, "offered_output_per_s": 2.5}


def test_block_counts_its_requests_whole_over_the_time_until_the_last_finishes():
    got = SERVE.end_to_end(None, _rec())
    assert got["output_tokens_per_s"] == pytest.approx(25 / 16.5)
    # 19 gaps of 0.1 s inside, then 1, 2, 2, 2 s: the three 2 s gaps after
    # the close hold the p95 of 23
    assert got["itl_p95_ms"] == pytest.approx(2000.0)
    assert got["ttft_p95_ms"] == pytest.approx(1e3 * (0.5 + 0.95 * 0.5))


@pytest.mark.parametrize("t_close", [T0 + 9.6, T0 + 10.0, T0 + 11.0, T0 + 20.0])
def test_the_close_s_place_among_the_stamps_moves_nothing(t_close):
    assert SERVE.end_to_end(None, _rec(t_close=t_close)) == SERVE.end_to_end(None, _rec())


@pytest.mark.parametrize("lead_shift", [-3.0, -1.2, 0.7, 2.6])
def test_the_open_s_place_among_the_lead_in_s_stamps_moves_nothing(lead_shift):
    assert SERVE.end_to_end(None, _rec(lead_shift=lead_shift)) == SERVE.end_to_end(None, _rec())


def test_lead_in_requests_count_nothing():
    rec = _rec()
    alone = dict(rec, requests=rec["requests"][1:])
    assert SERVE.end_to_end(None, alone) == SERVE.end_to_end(None, rec)
    s = SERVE.summary(rec)
    assert (s["output_tokens_counted"], s["gaps_counted"], s["ttft_counted"]) == (25, 23, 2)
    assert s["block_done_s"] == pytest.approx(16.5)
    assert s["output_tokens_in_window"] > 0 and s["lead_in"] == 1


def test_an_unanswered_request_of_the_block_reports_no_metric():
    rec = _rec()
    rec["requests"][2].req.done = False
    assert SERVE.end_to_end(None, rec) == dict.fromkeys(E2E)
    assert SERVE.summary(rec)["block_done_s"] is None
    rec["requests"][2].req.done = True
    rec["requests"][0].req.done = False  # the lead-in's: for the check alone
    assert SERVE.end_to_end(None, rec)["output_tokens_per_s"] is not None


def test_the_chat_block_is_580_tokens_of_7_requests():
    chat = json.loads((bench.HERE / "traffic" / "chat.json").read_text())
    block = [r for r in open_loop.schedule(chat, seed=2**33 + 1, seconds=51.0, vocab=151936)
             if r.due_s >= 0]
    assert len(block) == 7 and sum(r.max_new_tokens for r in block) == 580


def test_a_tiny_run_counts_every_token_of_the_requests_due_in_its_window():
    seed, seconds = 2**32 + 17, 3.0
    cell, driver = helpers.tiny_cell("tiny-qwen3", seed=seed, seconds=seconds)
    cell.name = "qwen3-0.6b.chat"  # the cell it stands for reports the metrics
    due = [r for r in open_loop.schedule(cell.traffic, seed=seed, seconds=seconds,
                                         vocab=cell.cfg.vocab_size) if r.due_s >= 0]
    want = sum(r.max_new_tokens for r in due)
    res, _ = bench.execute(cell, driver, helpers.MANIFEST)
    s = res["summary"]
    assert res["correct"], res["checks"]
    assert s["output_tokens_counted"] == want
    assert s["gaps_counted"] == want - len(due)
    assert s["ttft_counted"] == len(due) == res["attempted"]
    assert res["metrics"]["output_tokens_per_s"]["value"] == pytest.approx(
        want / s["block_done_s"])
