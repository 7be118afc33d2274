"""Open-loop arrivals of independent users: the same requests at the same
times for every seed, and token ids drawn from the seed.

The schedule repeats one block a window long (``seconds``). A block holds
n = round(rate_per_s * seconds) requests (at least one): arrival gaps at n
evenly spread quantiles of the exponential distribution (Poisson arrivals
at ``rate_per_s``), scaled to fill the block exactly, and prompt and output
lengths at n evenly spread quantiles of their lognormal distributions,
clipped. Gaps, prompt lengths and output lengths each take their own fixed
low-discrepancy order, so long prompts, long outputs and long gaps do not
come together. The seed draws every prompt's token ids. The window holds so
few requests that an order drawn from the seed would change its work by
tens of percent, so the order is fixed and a run's work, and so its time,
does not depend on the seed. Block 0 is the measured window; the blocks
before it make the lead-in, the last ``lead_in_s`` seconds before the window
opens, so that the window finds the engine as loaded as it stays.

Parameters (the traffic file):
  rate_per_s                     mean arrival rate
  prompt_tokens / output_tokens  {"median", "sigma", "min", "max"}
  lead_in_s                      seconds of traffic submitted before the window
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np


# irrational steps of the three low-discrepancy orders: gaps, prompts, outputs
_STEPS = ((math.sqrt(5) - 1) / 2, math.sqrt(2) - 1, math.sqrt(3) - 1)


@dataclass(frozen=True)
class Request:
    due_s: float  # offset from the window's start; negative in the lead-in
    prompt: list[int]
    max_new_tokens: int


def _levels(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_lengths(spec: dict, n: int) -> np.ndarray:
    z = np.array([NormalDist().inv_cdf(u) for u in _levels(n)])
    lengths = np.rint(spec["median"] * np.exp(spec["sigma"] * z))
    return np.clip(lengths, spec["min"], spec["max"]).astype(int)


def block_size(traffic: dict, seconds: float) -> int:
    return max(1, round(float(traffic["rate_per_s"]) * seconds))


def schedule(traffic: dict, *, seed: int, seconds: float, vocab: int) -> list[Request]:
    """Every request due from ``-lead_in_s`` up to ``seconds``, in due order."""
    n = block_size(traffic, seconds)
    gaps = -np.log(1.0 - _levels(n))
    prompts = lognormal_lengths(traffic["prompt_tokens"], n)
    outputs = lognormal_lengths(traffic["output_tokens"], n)
    lead = float(traffic.get("lead_in_s", 0.0))
    g, p, o = (np.argsort((0.5 + np.arange(n) * step) % 1.0) for step in _STEPS)
    offsets = seconds * np.concatenate([[0.0], np.cumsum(gaps[g])[:-1]]) / gaps.sum()
    rng = np.random.default_rng(seed)
    out: list[Request] = []
    for b in range(-math.ceil(lead / seconds), 1):
        for t, plen, olen in zip(b * seconds + offsets, prompts[p], outputs[o]):
            prompt = rng.integers(0, vocab, size=int(plen)).tolist()
            if t >= -lead:
                out.append(Request(float(t), prompt, int(olen)))
    return out
