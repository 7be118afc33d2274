"""Serving runtime: prefill + decode step factories (the dry-run's
``serve_step``) and a continuous-batching engine for the examples.

``make_serve_step`` builds the one-new-token step the decode_* shapes lower:
(params, caches, batch, pos) -> (next_token_logits, caches).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from ..models import attention
from ..models import transformer as tf
from .pspec import activation_policy
from .sharding import ShardingPolicy

import contextlib


@contextlib.contextmanager
def _null_ctx():
    yield


def _ctx(policy: Optional[ShardingPolicy]):
    if policy is None:
        return _null_ctx()
    return activation_policy(policy.mesh, policy.activation_specs())


def make_prefill(cfg: ArchConfig, policy: Optional[ShardingPolicy] = None):
    def prefill(params, batch):
        with _ctx(policy):
            logits, _ = tf.forward(cfg, params, batch)
        return logits

    return prefill


def make_serve_step(cfg: ArchConfig, policy: Optional[ShardingPolicy] = None):
    def serve_step(params, caches, batch, pos):
        with _ctx(policy):
            logits, caches = tf.decode_step(cfg, params, caches, batch, pos)
        return logits, caches

    return serve_step


# ---------------------------------------------------------- batching engine --
@dataclass
class Request:
    rid: int
    prompt: list[int]
    max_new_tokens: int
    generated: list[int] = field(default_factory=list)
    # next-token logits after the last prompt token (the decode path's
    # prefill result); the first generated token is their argmax
    prompt_logits: Optional[jax.Array] = None
    done: bool = False
    # time.perf_counter() stamps: queued, taken off the queue (before its
    # first prompt token is fed), first token read back on the host, done
    submitted_at: float = 0.0
    admitted_at: Optional[float] = None
    first_token_at: Optional[float] = None
    finished_at: float = 0.0


class ServingEngine:
    """Slot-based continuous batching over a fixed decode batch.

    Requests are queued, assigned to free slots, prefilled one-by-one into
    the shared KV cache at their slot index, and decoded in lockstep; slots
    recycle as requests finish (finished slots keep decoding into a junk
    position, masked out — standard continuous batching on a static shape).

    Each tick and its phases are ``jax.profiler.TraceAnnotation`` spans,
    recorded when a profile is: ``engine.step`` (one tick) holds
    ``engine.admit`` (a request taken off the queue and its prompt fed),
    ``engine.decode_call`` (a token batch built and the jitted decode
    dispatched; its child ``engine.writeback`` slices the slot's cache lane
    back in) and ``engine.sample`` (the next token read back to the host,
    where the host waits for the device). All but ``engine.step`` carry the
    request's id as ``rid``; ``engine.decode_call`` also carries ``kv_rows``,
    the cache rows its full-length attention layers read
    (``attention.decode_rows``).
    """

    def __init__(self, cfg: ArchConfig, params, *, batch_slots: int = 4,
                 max_len: int = 512, temperature: float = 0.0,
                 eos_token: Optional[int] = None, dtype=jnp.float32):
        self.cfg = cfg
        self.params = params
        self.slots: list[Optional[Request]] = [None] * batch_slots
        self.max_len = max_len
        self.temperature = temperature
        self.eos = eos_token
        self.queue: list[Request] = []
        self.finished: list[Request] = []
        self.caches = tf.init_cache(cfg, batch_slots, max_len, dtype)
        self.pos = [0] * batch_slots
        self._next_rid = 0
        self._decode = jax.jit(
            lambda p, c, b, pos: tf.decode_step(cfg, p, c, b, pos)
        )

    def submit(self, prompt: list[int], max_new_tokens: int = 32) -> int:
        rid = self._next_rid
        self._next_rid += 1
        self.queue.append(Request(rid, list(prompt), max_new_tokens,
                                  submitted_at=time.perf_counter()))
        return rid

    # -- internals ------------------------------------------------------------
    def _admit(self) -> None:
        for i, slot in enumerate(self.slots):
            if slot is None and self.queue:
                with jax.profiler.TraceAnnotation("engine.admit", rid=self.queue[0].rid):
                    req = self.queue.pop(0)
                    req.admitted_at = time.perf_counter()
                    self.slots[i] = req
                    self.pos[i] = 0
                    # prefill token-by-token into this slot's cache lane
                    # (simple and uniform across SSM/attention families)
                    for t in req.prompt:
                        req.prompt_logits = self._step_slot(i, t)

    def _step_slot(self, i: int, token: int) -> jax.Array:
        """Feed ``token`` to slot ``i`` at its position; returns that slot's
        next-token logits (vocab,)."""
        with jax.profiler.TraceAnnotation("engine.decode_call", rid=self.slots[i].rid,
                                          kv_rows=attention.decode_rows(self.pos[i], self.max_len)):
            batch = {"tokens": jnp.full((len(self.slots), 1), token, jnp.int32)}
            logits, caches = self._decode(
                self.params, self.caches, batch, jnp.int32(self.pos[i])
            )
            # Only slot i's cache lane must advance; others re-written with
            # the same values (decode writes every lane, but lanes are
            # independent: we slice the updated lane back in).
            with jax.profiler.TraceAnnotation("engine.writeback", rid=self.slots[i].rid):
                self.caches = jax.tree.map(
                    lambda old, new: jax.lax.dynamic_update_index_in_dim(
                        old, jax.lax.dynamic_index_in_dim(new, i, 1, keepdims=False), i, 1
                    )
                    if old.ndim >= 2
                    else new,
                    self.caches,
                    caches,
                )
            self.pos[i] += 1
            return logits[i, -1]

    def step(self) -> None:
        """One engine tick: admit + one token for every active slot (the
        first token comes from the prompt's logits, later ones from a decode
        step on the previous token)."""
        with jax.profiler.TraceAnnotation("engine.step"):
            self._admit()
            for i, req in enumerate(self.slots):
                if req is None:
                    continue
                if req.generated:
                    logits = self._step_slot(i, req.generated[-1])
                else:
                    logits = req.prompt_logits
                with jax.profiler.TraceAnnotation("engine.sample", rid=req.rid):
                    nxt = int(jnp.argmax(logits))
                if not req.generated:
                    req.first_token_at = time.perf_counter()
                req.generated.append(nxt)
                if len(req.generated) >= req.max_new_tokens or (
                    self.eos is not None and nxt == self.eos
                ):
                    req.done = True
                    req.finished_at = time.perf_counter()
                    self.finished.append(req)
                    self.slots[i] = None

    def run_until_drained(self, max_ticks: int = 10_000) -> list[Request]:
        ticks = 0
        while (self.queue or any(self.slots)) and ticks < max_ticks:
            self.step()
            ticks += 1
        return self.finished
