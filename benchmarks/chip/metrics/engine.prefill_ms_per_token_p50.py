"""Median prefill time per prompt token: from a request's leaving the queue
to its first token read back on the host (``Request.first_token_at -
Request.admitted_at``, the engine's own ``perf_counter`` stamps), over its
prompt length; over the requests admitted in the window that have a first
token. Nothing where the engine does not stamp them."""
import numpy as np


def read(run):
    t0, close = run.rec["t0"], run.rec["t_close"]
    per_token = []
    for tr in run.rec["requests"]:
        admitted = getattr(tr.req, "admitted_at", None)
        first = getattr(tr.req, "first_token_at", None)
        if admitted is not None and first is not None and t0 <= admitted <= close:
            per_token.append((first - admitted) / len(tr.req.prompt))
    return 1e3 * float(np.median(per_token)) if per_token else None
