"""Open-loop serving through ``runtime.serve.ServingEngine``: requests are
submitted when due and the engine ticks (``step``) while it has work.

Set-up makes the weights, builds the engine, warms it up on every slot and
drives the traffic's lead-in, so that the window opens on an engine as
loaded as it stays. Through lead-in, window and drain the driver stamps, by
the host clock, each request's due time, the start of the tick that
admitted it, and the end of the tick that produced each of its tokens (the
engine's ``step`` returns only after it has read every new token back to
the host). The window closes at the end of the last tick that started
inside it; no request is submitted after that, and the engine drains the
open ones, for up to ``DRAIN_S``, stamping as in the window.

The end-to-end metrics count one whole schedule block: the requests due in
the window, every one of their tokens whenever it is read back, the drain
included, and none of the lead-in's. Their throughput runs until the last
of them finishes. Where the window's edges fall among the stamps moves none
of them.

Correct means: every request submitted is answered in full, each with
exactly its number of tokens, all in the vocabulary; and on a sample of
finished requests drawn from the seed (the longest among them) the plain
reference, run once over each prompt with its served tokens, puts no served
token further below its best logit than the limit, and agrees with the
engine's logits after each prompt (the decode path through the cache)
within the limit.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from .. import reference as ref
from .. import weights, work
from ..traffic import open_loop

SAMPLE_REQUESTS = 4  # the reference runs them as one (4, max_len) batch
DRAIN_S = 60.0


def setup(cell):
    from repro.runtime.serve import ServingEngine

    eng_cfg = cell.config["engine"]
    params = weights.make(cell.cfg, cell.seed, jnp.bfloat16)
    engine = ServingEngine(cell.cfg, params, batch_slots=eng_cfg["batch_slots"],
                           max_len=eng_cfg["max_len"], dtype=jnp.bfloat16)
    # warm-up: one short request per slot compiles the decode step and the
    # engine's per-slot cache write-back and sampling
    for i in range(eng_cfg["batch_slots"]):
        engine.submit([i + 1, i + 2], max_new_tokens=2)
    engine.run_until_drained()
    engine.finished.clear()
    jax.block_until_ready(engine.caches)
    reqs = open_loop.schedule(cell.traffic, seed=cell.seed, seconds=cell.seconds,
                              vocab=cell.cfg.vocab_size)
    return {"params": params, "engine": engine, "schedule": reqs}


class _Tracked:
    __slots__ = ("req", "due", "admitted", "tokens", "prompt_len")

    def __init__(self, req, due):
        self.req, self.due, self.admitted, self.tokens = req, due, None, []
        self.prompt_len = len(req.prompt)


def _tick(engine, active, m, clock):
    """One engine tick: stamps each new token with the tick's end, and a
    request's admission with its start; returns the requests still open and
    the tick's (start, end, flops, bytes, calls)."""
    ts = time.perf_counter()
    with clock.span("bench.step"):
        engine.step()
    te = time.perf_counter()
    flops = nbytes = 0.0
    calls = 0
    still = []
    for tr in active:
        n = len(tr.req.generated)
        new = n - len(tr.tokens)
        if new:
            if not tr.tokens:  # admitted this tick: prefilled at 0..P-1
                tr.admitted = ts
                positions = range(tr.prompt_len)
            else:  # fed its previous token at P + k - 1
                positions = [tr.prompt_len + len(tr.tokens) - 1]
            for pos in positions:
                f, b = work.decode_call(m, pos)
                flops += f
                nbytes += b
                calls += 1
            tr.tokens.extend([te] * new)
        if not tr.req.done:
            still.append(tr)
    return still, (ts, te, flops, nbytes, calls)


def window(cell, state, clock, drain_s=DRAIN_S):
    """Drive the lead-in, then the window of ``cell.seconds``; returns the
    host records. Finishes the requests still open at the close for up to
    ``drain_s`` more, with ticks stamped as in the window."""
    engine, reqs = state["engine"], state["schedule"]
    m = cell.model
    t0 = time.perf_counter() + float(cell.traffic.get("lead_in_s", 0.0))
    end = t0 + cell.seconds
    opened = False
    nxt = 0
    active: list[_Tracked] = []
    tracked: list[_Tracked] = []
    ticks = []  # ticks started in the window: (start, end, flops, bytes, calls)
    lateness = []
    while True:
        now = time.perf_counter()
        if now >= end:
            break
        if not opened and now >= t0:
            clock.start_window(t0)
            opened = True
        if opened:
            clock.maybe_trace(now)
        with clock.span("bench.submit"):
            while nxt < len(reqs) and t0 + reqs[nxt].due_s <= now:
                r = reqs[nxt]
                engine.submit(r.prompt, max_new_tokens=r.max_new_tokens)
                tr = _Tracked(engine.queue[-1], t0 + r.due_s)
                lateness.append(now - tr.due)
                active.append(tr)
                tracked.append(tr)
                nxt += 1
        if not active:
            wake = min(t0 + reqs[nxt].due_s if nxt < len(reqs) else end, end)
            if not opened:
                wake = min(wake, t0)
            with clock.span("bench.wait"):
                time.sleep(max(0.0, wake - time.perf_counter()))
            continue
        active, tick = _tick(engine, active, m, clock)
        if tick[0] >= t0:
            ticks.append(tick)
    if not opened:
        clock.start_window(t0)
    t_close = ticks[-1][1] if ticks and ticks[-1][1] > end else end
    clock.end_window(t_close)
    # due while the last tick ran: submitted at the close, and waited for
    # since their due time
    for r in reqs[nxt:]:
        engine.submit(r.prompt, max_new_tokens=r.max_new_tokens)
        tr = _Tracked(engine.queue[-1], t0 + r.due_s)
        lateness.append(t_close - tr.due)
        active.append(tr)
        tracked.append(tr)
    jax.block_until_ready(engine.caches)
    clock.stop_trace()
    # the drain: no arrivals, the open requests finished up to drain_s past
    # the close; its stamps count for the requests due in the window
    while active and time.perf_counter() < t_close + drain_s:
        active, _ = _tick(engine, active, m, clock)
    offered = sum(r.max_new_tokens for r in reqs if r.due_s >= 0) / cell.seconds
    return {"t0": t0, "t_close": t_close, "ticks": ticks, "requests": tracked,
            "lateness": lateness, "scheduled": len(reqs), "offered_output_per_s": offered}


def _in_window(rec, t) -> bool:
    return rec["t0"] < t <= rec["t_close"]


def _block(rec) -> dict:
    """The measured set, one whole schedule block: the requests due in the
    window, each with every token it was read back, whenever (the drain
    included). ``t_done`` is the last of their stamps, None while one of
    them is unanswered."""
    block = [tr for tr in rec["requests"] if tr.due >= rec["t0"]]
    whole = bool(block) and all(tr.req.done for tr in block)
    return {
        "tokens": sum(len(tr.tokens) for tr in block),
        "gaps": [b - a for tr in block for a, b in zip(tr.tokens, tr.tokens[1:])],
        "ttft": [tr.tokens[0] - tr.due for tr in block if tr.tokens],
        "t_done": max(tr.tokens[-1] for tr in block) if whole else None,
    }


def end_to_end(cell, rec) -> dict:
    """Rates and tails of one whole schedule block: its output tokens over
    the time from the window's open until the last of them is read back,
    every gap between consecutive tokens of a request, and every request's
    first-token wait from its due time. Nothing while one of them is
    unanswered (the run is then not correct)."""
    b = _block(rec)
    if b["t_done"] is None:
        return dict.fromkeys(("output_tokens_per_s", "ttft_p95_ms", "itl_p95_ms"))
    return {
        "output_tokens_per_s": b["tokens"] / (b["t_done"] - rec["t0"]),
        "ttft_p95_ms": 1e3 * float(np.percentile(b["ttft"], 95)),
        "itl_p95_ms": 1e3 * float(np.percentile(b["gaps"], 95)) if b["gaps"] else None,
    }


def summary(rec) -> dict:
    reqs, late = rec["requests"], rec["lateness"]
    t0, t_close = rec["t0"], rec["t_close"]
    due = [tr for tr in reqs if tr.due >= t0]
    b = _block(rec)
    return {
        "scheduled": rec["scheduled"], "lead_in": len(reqs) - len(due), "due": len(due),
        "open_at_start": sum(1 for tr in reqs if tr.due < t0 and not (
            tr.req.done and tr.tokens[-1] <= t0)),
        "first_token_in_window": sum(1 for tr in due if tr.tokens and tr.tokens[0] <= t_close),
        "finished_in_window": sum(1 for tr in reqs if tr.req.done
                                  and _in_window(rec, tr.tokens[-1])),
        "queued_at_close": sum(1 for tr in reqs if tr.admitted is None or tr.admitted > t_close),
        "unanswered": sum(1 for tr in reqs if not tr.req.done),
        "output_tokens_in_window": sum(1 for tr in reqs for t in tr.tokens
                                       if _in_window(rec, t)),
        "output_tokens_counted": b["tokens"], "gaps_counted": len(b["gaps"]),
        "ttft_counted": len(b["ttft"]),
        "block_done_s": None if b["t_done"] is None else b["t_done"] - t0,
        "offered_output_tokens_per_s": rec["offered_output_per_s"],
        "ticks": len(rec["ticks"]),
        "submit_late_ms_p50": 1e3 * float(np.median(late)) if late else 0.0,
        "submit_late_ms_max": 1e3 * max(late) if late else 0.0,
    }


def release(state) -> None:
    """Free the engine's cache before the reference runs."""
    state["engine"].caches = None
    state.pop("engine")


def _sample(cell, rec):
    done = [tr for tr in rec["requests"] if tr.req.done]
    if not done:
        return []
    rng = np.random.default_rng([cell.seed, 1])
    longest = max(done, key=lambda tr: tr.prompt_len + len(tr.req.generated))
    rest = [tr for tr in done if tr is not longest]
    pick = rng.choice(len(rest), size=min(len(rest), SAMPLE_REQUESTS - 1), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def served_logits(cell, params, tokens, quant=None):
    """Reference logits (n, max_len, vocab) over the padded token rows."""
    m, cfg = cell.model, cell.cfg
    x = ref.embed(params["embed"], tokens, quant)
    x = ref.layers(m, params["blocks"][0], x, quant)
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    return ref.logits(params["final_norm"], head, x, eps=m["norm_eps"],
                      tied=cfg.tie_embeddings, quant=quant)


@jax.jit
def _gaps(lg, rows, pos, tok):
    """Per compared token: how far the reference's logit of ``tok`` lies
    below its best; and the reference's top token there."""
    at = lg[rows, pos]  # (n, vocab)
    return jnp.max(at, axis=-1) - at[jnp.arange(at.shape[0]), tok], jnp.argmax(at, axis=-1)


@jax.jit
def _err_over_rms(got, want):
    """Largest |got - want| of each row over the row's root-mean-square."""
    want = want.astype(jnp.float32)
    rms = jnp.sqrt(jnp.mean(want * want, axis=-1))
    return jnp.max(jnp.max(jnp.abs(got.astype(jnp.float32) - want), axis=-1) / rms)


def check(cell, state, rec, controls=()) -> dict:
    """Every due request answered in full, and on the sample: served tokens
    and the decode path's logits after the prompt against the reference.
    With ``controls``, the same numbers for the reference computed in lower
    precision, read at the same positions."""
    cfg, params = cell.cfg, state["params"]
    max_len = cell.config["engine"]["max_len"]
    reqs = rec["requests"]
    bad = sum(1 for tr in reqs if tr.req.done and (
        len(tr.req.generated) != tr.req.max_new_tokens
        or any(not 0 <= t < cfg.vocab_size for t in tr.req.generated)))
    out = {"unanswered_requests": {"value": sum(1 for tr in reqs if not tr.req.done), "limit": 0},
           "malformed_requests": {"value": bad, "limit": 0}}
    sample = _sample(cell, rec)
    tokens = np.zeros((SAMPLE_REQUESTS, max_len), np.int32)
    rows, pos, tok, last = [], [], [], []
    for i, tr in enumerate(sample):
        seq = tr.req.prompt + tr.req.generated[:-1]
        tokens[i, :len(seq)] = seq
        last.append(tr.prompt_len - 1)
        for j, t in enumerate(tr.req.generated):
            rows.append(i)
            pos.append(tr.prompt_len - 1 + j)
            tok.append(t)
    out["compared_requests"] = len(sample)
    out["compared_tokens"] = len(tok)
    if not sample:
        out["served_gap_max"] = {"value": float("inf"), "limit": cell.limit("served_gap_max")}
        return out
    rows, pos, tok = (jnp.asarray(a, jnp.int32) for a in (rows, pos, tok))
    at_prompt = (jnp.arange(len(sample)), jnp.asarray(last, jnp.int32))
    prompt_logits = jnp.stack([tr.req.prompt_logits for tr in sample])
    lg = served_logits(cell, params, jnp.asarray(tokens))
    out["served_gap_max"] = {"value": float(jnp.max(_gaps(lg, rows, pos, tok)[0])),
                             "limit": cell.limit("served_gap_max")}
    out["prompt_logit_err"] = {"value": float(_err_over_rms(prompt_logits, lg[at_prompt])),
                               "limit": cell.limit("prompt_logit_err")}
    for q in controls:
        c_lg = served_logits(cell, params, jnp.asarray(tokens), q)
        top = _gaps(c_lg, rows, pos, tok)[1]
        out[f"control_{q}.served_gap_max"] = float(jnp.max(_gaps(lg, rows, pos, top)[0]))
        out[f"control_{q}.prompt_logit_err"] = float(_err_over_rms(c_lg[at_prompt], lg[at_prompt]))
        del c_lg
    return out
