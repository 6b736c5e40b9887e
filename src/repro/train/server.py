"""Batched serving with continuous-batching slots.

A fixed decode batch of ``n_slots``; requests are prefilled individually
(disaggregated prefill), inserted into free slots of the live batched cache
(per-sequence positions — slots run at different depths), and decoded
together.  Finished slots free immediately and new requests join without
draining the batch.

The insert is one jitted program, ``serve_insert``, beside
``serve_prefill`` and ``serve_decode``: it donates the batched cache and
takes the slot as a traced scalar, so one compile serves every slot and
XLA writes the request's slot in place.  Admission therefore holds one
batched cache, not two, and moves one slot's bytes, not the whole cache's.

Latency accounting is end-to-end: ``Request.latency_s`` runs from
``submit()`` to finish, with a ``queue_s`` / ``prefill_s`` / ``decode_s``
breakdown per request — an SLA on p99 latency is meaningless if queue wait
and prefill are invisible, which is exactly what the pre-fix timer (started
after prefill, at admission) got wrong.

Each ``step()`` emits ``repro.obs`` spans into the ambient tracer
(``serve.step`` and, inside it, ``serve.admit`` per request with its
``serve.prefill`` / ``serve.insert`` / ``serve.first_token``, then, when
a slot is active, ``serve.decode``, ``serve.decode_sync`` and
``serve.emit``).  The spans sit at the host syncs the step already has
and add none; under the default no-op tracer each costs one call.

A model whose expert layers count (``ArchConfig.counts_moe``) has its
prefill and decode programs hand back the counters of
:data:`repro.models.moe.COUNTERS`, summed over the layers; with the tracer
enabled they are read at the sync that follows (``serve.first_token``,
``serve.decode_sync``) and added to the args of the ``serve.prefill`` and
``serve.decode`` spans as ``moe_<counter>`` (``set_args``).  An untraced
step never reads them.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Callable, Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.models import moe as MOE
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


def _record_counts(tr, span, counts) -> None:
    """With the tracer enabled, the program's MoE counters (``counts``: []
    or [an int32 array of ``COUNTERS``]) as args of ``span``; the step has
    synced already, so reading them waits for nothing."""
    if tr.enabled and counts:
        span.set_args(**dict(zip((f"moe_{k}" for k in MOE.COUNTERS),
                                 np.asarray(counts[0]).tolist())))


def _insert_slot(cache, req_cache, slot):
    """Copy a single-request cache into batch slot ``slot`` (an int or a
    traced int32 scalar); the pure function ``serve_insert`` jits."""

    def ins(batched, single):
        if batched.ndim == 1:        # pos: (B,)
            return batched.at[slot].set(single[0])
        # layer leaves: (R, B, ...)
        return jax.lax.dynamic_update_slice_in_dim(
            batched, single, slot, axis=1)

    return jax.tree.map(ins, cache, req_cache)


class Server:
    """Continuous-batching server; see the module docstring."""

    def __init__(self, params, cfg: T.ArchConfig, n_slots: int = 4,
                 max_len: int = 512,
                 decode_fn: Optional[Callable] = None):
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

        def serve_decode(p, c, t):
            return T.decode_step(p, c, t, cfg)

        def serve_prefill(p, b):
            return T.prefill(p, b, cfg, max_len)

        def serve_insert(c, rc, slot):
            return _insert_slot(c, rc, slot)

        self._decode = decode_fn or jax.jit(serve_decode, donate_argnums=(1,))
        self._prefill = jax.jit(serve_prefill)
        self._insert = jax.jit(serve_insert, donate_argnums=(0,))

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

    def _admit(self, tr) -> None:
        """Prefill and slot every queued request a free slot takes."""
        while self.free and self.queue:
            req = self.queue.popleft()
            slot = self.free.pop()
            with tr.span("serve.admit", uid=req.uid,
                         prompt_len=len(req.prompt)):
                req.admit_s = time.perf_counter()
                req.queue_s = req.admit_s - req.submit_s
                batch = {"tokens": jnp.asarray(req.prompt[None, :],
                                               jnp.int32)}
                if self.cfg.vision_prefix:
                    batch["patches"] = jnp.zeros(
                        (1, self.cfg.vision_prefix, self.cfg.d_model),
                        self.cfg.dtype)
                if self.cfg.enc_dec:
                    batch["frames"] = jnp.zeros(
                        (1, self.cfg.enc_seq, self.cfg.d_model),
                        self.cfg.dtype)
                with tr.span("serve.prefill") as prefill_span:
                    logits, rc, *counts = self._prefill(self.params, batch)
                with tr.span("serve.insert"):
                    self.cache = self._insert(self.cache, rc,
                                              np.int32(slot))
                with tr.span("serve.first_token"):
                    # also syncs the prefill
                    first = int(jnp.argmax(logits[0]))
                    _record_counts(tr, prefill_span, counts)
                req.prefill_s = time.perf_counter() - req.admit_s
            req.output = [first]
            req.status = ACTIVE
            self.last_tok[slot, 0] = first
            self.active[slot] = req
            self.new_counts[slot] = 1

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
        """Admit what the free slots take, then one decode step for all
        active slots; returns finished requests."""
        tr = obs.current()
        with tr.span("serve.step"):
            return self._step(tr)

    def _step(self, tr) -> List[Request]:
        self._admit(tr)
        if not self.active:
            return []
        # the slots decoded and the positions they attend, summed
        args = {} if not tr.enabled else {
            "active": len(self.active),
            "context": sum(len(r.prompt) + len(r.output)
                           for r in self.active.values())}
        with tr.span("serve.decode", **args) as decode_span:
            logits, self.cache, *counts = self._decode(
                self.params, self.cache, jnp.asarray(self.last_tok))
        with tr.span("serve.decode_sync"):
            toks = np.asarray(jnp.argmax(logits, axis=-1))
            _record_counts(tr, decode_span, counts)
        done: List[Request] = []
        with tr.span("serve.emit"):
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
