"""Median length of one ``ServingEngine.step()`` in the window, by the
benchmark's host-clock span around the call."""
import numpy as np


def read(run):
    ticks = run.rec.get("ticks", [])
    return 1e3 * float(np.median([te - ts for ts, te, *_ in ticks])) if ticks else None
