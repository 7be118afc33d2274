"""Median time a request waited, from its due time to the start of the
engine tick that admitted it, by the benchmark's host clock; over the
requests admitted in the window."""
import numpy as np


def read(run):
    t0, close = run.rec["t0"], run.rec["t_close"]
    waits = [tr.admitted - tr.due for tr in run.rec["requests"]
             if tr.admitted is not None and t0 <= tr.admitted <= close]
    return 1e3 * float(np.median(waits)) if waits else None
