"""Batched serving with continuous-batching slots.

A fixed decode batch of ``n_slots``; requests are prefilled individually
(disaggregated prefill), inserted into free slots of the live batched cache
(per-sequence positions — slots run at different depths), and decoded
together.  Finished slots free immediately and new requests join without
draining the batch.

Latency accounting is end-to-end: ``Request.latency_s`` runs from
``submit()`` to finish, with a ``queue_s`` / ``prefill_s`` / ``decode_s``
breakdown per request — an SLA on p99 latency is meaningless if queue wait
and prefill are invisible, which is exactly what the pre-fix timer (started
after prefill, at admission) got wrong.

Idle capacity is a first-class resource: a ``best_effort`` hook (one small
chunk of background work per call — e.g. one candidate measurement of an
online tuning session, see :mod:`repro.compiler.serve_tune`) runs only when
the queue is empty and at least one decode slot is free, so live requests
always preempt background work at chunk granularity.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, Callable, Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import transformer as T

# Request.status values, in lifecycle order.
QUEUED, ACTIVE, DONE, REJECTED, ABANDONED = (
    "queued", "active", "done", "rejected", "abandoned")


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray            # (L,) int32
    max_new_tokens: int = 16
    eos_id: Optional[int] = None
    # filled by the server
    output: Optional[List[int]] = None
    status: str = QUEUED
    error: Optional[str] = None
    # end-to-end latency (submit -> finish) + its breakdown; all None until
    # the request finishes (or forever, for rejected/abandoned requests)
    latency_s: Optional[float] = None
    queue_s: Optional[float] = None
    prefill_s: Optional[float] = None
    decode_s: Optional[float] = None
    # internal timeline stamps (perf_counter): set by submit()/_admit()
    submit_s: Optional[float] = None
    admit_s: Optional[float] = None
    finish_s: Optional[float] = None

    @property
    def ok(self) -> bool:
        return self.status == DONE


def _insert_slot(cache, req_cache, slot: int):
    """Copy a single-request cache into batch slot ``slot``."""

    def ins(batched, single):
        if batched.ndim == 1:        # pos: (B,)
            return batched.at[slot].set(single[0])
        # layer leaves: (R, B, ...)
        return jax.lax.dynamic_update_slice_in_dim(
            batched, single, slot, axis=1)

    return jax.tree.map(ins, cache, req_cache)


class Server:
    """Continuous-batching server; see the module docstring.

    ``best_effort`` is an optional callable ``(server) -> bool`` invoked
    from :meth:`step` whenever there is idle capacity (queue empty AND at
    least one free slot).  It must do at most one *small* chunk of work
    per call and return True if it did any — the server never calls it
    while requests wait, which is the admission-aware preemption contract
    background measurement schedulers rely on.
    """

    def __init__(self, params, cfg: T.ArchConfig, n_slots: int = 4,
                 max_len: int = 512,
                 decode_fn: Optional[Callable] = None,
                 greedy: bool = True,
                 best_effort: Optional[Callable[["Server"], bool]] = None):
        self.params, self.cfg = params, cfg
        self.n_slots, self.max_len = n_slots, max_len
        self.cache = T.init_cache(cfg, n_slots, max_len)
        self.free = list(range(n_slots))
        self.active: Dict[int, Request] = {}
        self.last_tok = np.zeros((n_slots, 1), np.int32)
        self.new_counts: Dict[int, int] = {}
        self.queue: Deque[Request] = deque()
        self.rejected: List[Request] = []
        self.abandoned: List[Request] = []
        self.best_effort = best_effort
        self._decode = decode_fn or jax.jit(
            lambda p, c, t: T.decode_step(p, c, t, cfg), donate_argnums=(1,))
        self._prefill = jax.jit(
            lambda p, b: T.prefill(p, b, cfg, max_len),
            static_argnums=())

    def prefill_programs(self) -> int:
        """Prefill programs compiled so far: prefill is jitted on the exact
        prompt shape, so one per distinct prompt length."""
        return self._prefill._cache_size()

    # ------------------------------------------------------------- intake
    def submit(self, req: Request) -> Request:
        """Queue ``req`` (stamping its end-to-end latency clock), or fail
        it gracefully: an oversized or empty prompt is rejected here with
        ``status="rejected"`` + an ``error`` instead of corrupting the
        batched cache at admission (prefill pads the cache to ``max_len``;
        a longer prompt would silently truncate/overwrite it)."""
        req.submit_s = time.perf_counter()
        if len(req.prompt) == 0:
            req.status, req.error = REJECTED, "empty prompt"
        elif len(req.prompt) >= self.max_len:
            req.status, req.error = REJECTED, (
                f"prompt length {len(req.prompt)} >= max_len "
                f"{self.max_len}: no room in the slot cache")
        if req.status == REJECTED:
            req.output = []
            self.rejected.append(req)
            return req
        req.status = QUEUED
        self.queue.append(req)
        return req

    def _admit(self):
        while self.free and self.queue:
            req = self.queue.popleft()
            slot = self.free.pop()
            req.admit_s = time.perf_counter()
            req.queue_s = req.admit_s - req.submit_s
            batch = {"tokens": jnp.asarray(req.prompt[None, :], jnp.int32)}
            if self.cfg.vision_prefix:
                batch["patches"] = jnp.zeros(
                    (1, self.cfg.vision_prefix, self.cfg.d_model),
                    self.cfg.dtype)
            if self.cfg.enc_dec:
                batch["frames"] = jnp.zeros(
                    (1, self.cfg.enc_seq, self.cfg.d_model), self.cfg.dtype)
            logits, rc = self._prefill(self.params, batch)
            self.cache = _insert_slot(self.cache, rc, slot)
            first = int(jnp.argmax(logits[0]))   # also syncs the prefill
            req.prefill_s = time.perf_counter() - req.admit_s
            req.output = [first]
            req.status = ACTIVE
            self.last_tok[slot, 0] = first
            self.active[slot] = req
            self.new_counts[slot] = 1

    # ---------------------------------------------------------- idle work
    def idle_capacity(self) -> int:
        """Free decode slots available for best-effort work right now —
        zero whenever any request is waiting for admission (live traffic
        preempts background measurements)."""
        return 0 if self.queue else len(self.free)

    def _tick_best_effort(self) -> bool:
        if self.best_effort is None or not self.idle_capacity():
            return False
        return bool(self.best_effort(self))

    # ------------------------------------------------------------- decode
    def _finish(self, slot: int, status: str = DONE) -> Request:
        req = self.active.pop(slot)
        req.finish_s = time.perf_counter()
        req.status = status
        # end-to-end: queue wait + prefill + decode (the pre-fix timer
        # started at admission *after* prefill and missed the first two)
        req.latency_s = req.finish_s - req.submit_s
        req.decode_s = req.finish_s - req.admit_s - req.prefill_s
        self.new_counts.pop(slot)
        self.free.append(slot)
        return req

    def step(self) -> List[Request]:
        """One decode step for all active slots; returns finished requests.
        With idle capacity (free slots + empty queue) one chunk of
        best-effort work runs first — alongside the decode when other
        slots are busy, or alone when the server is idle."""
        self._admit()
        self._tick_best_effort()
        if not self.active:
            return []
        logits, self.cache = self._decode(
            self.params, self.cache, jnp.asarray(self.last_tok))
        toks = np.asarray(jnp.argmax(logits, axis=-1))
        done: List[Request] = []
        for slot, req in list(self.active.items()):
            t = int(toks[slot])
            req.output.append(t)
            self.last_tok[slot, 0] = t
            self.new_counts[slot] += 1
            ended = (req.eos_id is not None and t == req.eos_id)
            full = (self.new_counts[slot] >= req.max_new_tokens)
            too_long = (len(req.prompt) + self.new_counts[slot]
                        >= self.max_len - 1)
            if ended or full or too_long:
                done.append(self._finish(slot))
        return done

    def run_until_drained(self, max_steps: int = 10000) -> List[Request]:
        """Serve until queue + slots are empty.  Hitting ``max_steps``
        with requests still in flight is not silent: every live request
        is marked ``status="abandoned"`` (latency fields stay None), the
        slots are reclaimed, and the abandoned list is returned alongside
        the server's ``abandoned`` attribute — callers must report them,
        not average over their ``None`` latencies."""
        out: List[Request] = []
        for _ in range(max_steps):
            out.extend(self.step())
            if not self.active and not self.queue:
                return out
        for slot in sorted(self.active):
            req = self._finish(slot, status=ABANDONED)
            req.latency_s = req.decode_s = None   # never finished
            self.abandoned.append(req)
        while self.queue:
            req = self.queue.popleft()
            req.status = ABANDONED
            self.abandoned.append(req)
        return out
