"""Serve path: the program's ``Server`` (``repro.train.server``) fed by the
harness's clock.

Set-up makes the seeded weights, builds the server at the configuration's
geometry, and serves one short request per prompt length the mix sends,
so every prefill shape, the slot insert and the decode step are compiled
or loaded from the cache before the window opens.

The window drives ``Server.submit`` and ``Server.step`` from one thread.
``open_loop`` submits each request when it falls due and keeps serving
after the last arrival until every request has finished; ``saturated``
keeps the queue ``queue_depth`` deep until the window closes, then drains
what it admitted. Each output token is stamped at the return of the
``step()`` that produced it; a request's first-token time runs from its
scheduled arrival. The mean first-token time is the end-to-end metric; its
tail is read beside it (``server.ttft_p95_ms``), because the p95 of ~120
requests swings with how many arrivals share a step's prefills.

After the window the server's arrays are freed and the float32 reference
re-runs a sample of the finished requests (the longest among them), one
layer at a time, to read how far each served token's logit lies below
the reference's best.
"""
from __future__ import annotations

import time
from typing import Dict, List

import jax
import numpy as np

from bench import program, traffic
from bench.common import Check, Run, SubTrace, memory_peak_bytes
from bench.reference import dense
from repro.train.server import DONE, Request, Server

DRAIN_LIMIT_S = 120.0   # serving after the close before a request is failed


class _Live:
    __slots__ = ("req", "due", "submit", "stamps")

    def __init__(self, req: Request, due: float, submit: float):
        self.req, self.due, self.submit = req, due, submit
        self.stamps: List[float] = []


def _warm(srv: Server, lengths, vocab: int) -> None:
    for i, n in enumerate(lengths):
        srv.submit(Request(uid=-1 - i, prompt=np.arange(n, dtype=np.int32)
                           % vocab, max_new_tokens=2))
    srv.run_until_drained()
    if srv.rejected or srv.abandoned:
        raise RuntimeError("warm-up requests failed")


def _step(srv: Server, live: Dict[int, _Live], steps: list) -> None:
    """One ``Server.step``; stamps the tokens it produced and records what
    it did: requests admitted (prefilled), and the sequences decoded with
    their summed context (new token included)."""
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.serve.step"):
        done = srv.step()
    t1 = time.perf_counter()
    admitted = decoded = context = 0
    for r in list(srv.active.values()) + done:
        rec = live[r.uid]
        k = len(r.output) - len(rec.stamps)
        if k and not rec.stamps:
            admitted += 1
        if k == 2 or (k == 1 and rec.stamps):
            decoded += 1
            context += len(r.prompt) + len(r.output) - 1
        rec.stamps.extend([t1] * k)
    steps.append({"t0": t0, "t1": t1, "admitted": admitted,
                  "decoded": decoded, "context": context})


def _submit(srv: Server, live: Dict[int, _Live], p: traffic.Planned,
            due: float) -> None:
    now = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.serve.submit"):
        req = srv.submit(Request(uid=p.uid, prompt=p.prompt,
                                 max_new_tokens=p.max_new))
    live[p.uid] = _Live(req, due, now)


def _busy(srv: Server) -> bool:
    return bool(srv.active or srv.queue)


def _open_loop(srv, plan, seconds, live, steps, tracer=None):
    t_open = time.perf_counter()
    i, n = 0, len(plan)
    while True:
        now = time.perf_counter()
        if tracer:
            tracer.poll(now - t_open)
        while i < n and t_open + plan[i].due_s <= now:
            _submit(srv, live, plan[i], t_open + plan[i].due_s)
            i += 1
        if not _busy(srv):
            if i == n:
                break
            with jax.profiler.TraceAnnotation("bench.serve.wait"):
                time.sleep(max(0.0, t_open + plan[i].due_s
                               - time.perf_counter()))
            continue
        if now - t_open > seconds + DRAIN_LIMIT_S:
            break
        _step(srv, live, steps)
    return t_open


def _saturated(srv, gen, depth, seconds, live, steps, tracer):
    t_open = time.perf_counter()
    while True:
        now = time.perf_counter()
        tracer.poll(now - t_open)
        if now < t_open + seconds:
            while len(srv.queue) < depth:
                _submit(srv, live, next(gen), time.perf_counter())
        elif not _busy(srv) or now - t_open > seconds + DRAIN_LIMIT_S:
            break
        _step(srv, live, steps)
    return t_open


def _tokens_in_window(live, t_close) -> int:
    n = 0
    for rec in live.values():
        if rec.stamps and rec.stamps[0] <= t_close:
            n += len(rec.req.prompt)
        n += sum(1 for t in rec.stamps if t <= t_close)
    return n


def _sample(finished: List[_Live], k: int, seed: int) -> List[_Live]:
    """The request with the longest output, and ``k - 1`` others drawn
    from the seed."""
    longest = max(finished, key=lambda r: (len(r.req.output), r.req.uid))
    rest = sorted((r for r in finished if r is not longest),
                  key=lambda r: r.req.uid)
    rng = np.random.default_rng([int(seed) & (2 ** 64 - 1), 7])
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[j] for j in sorted(pick)]


def gap_readings(gaps) -> Dict[str, float]:
    """What the check can compare, over every served token of the sample:
    the widest and the mean gap below the reference's best, and the share
    of tokens the reference does not rank first."""
    g = np.concatenate(gaps)
    return {"logit_gap_max": float(g.max()),
            "logit_gap_mean": float(g.mean()),
            "not_first_share": float((g > 0).mean())}


def run(ctx) -> Run:
    c, mix, seed, seconds = ctx.config, ctx.mix, ctx.seed, ctx.seconds
    cfg = program.arch_config(c, reduced=ctx.reduced)
    spec = program.spec_of(cfg, c["tie_word_embeddings"])
    geo = c["serve"]
    params = program.make_params(cfg, seed, spec.tied)
    srv = Server(params, cfg, n_slots=geo["slots"], max_len=geo["max_len"])
    _warm(srv, traffic.exercised_lengths(mix, seconds), cfg.vocab)
    if mix["mode"] == "open_loop":
        plan = traffic.open_loop(mix, seed, seconds, cfg.vocab)
    else:
        gen = traffic.saturated(mix, seed, cfg.vocab)
    jax.block_until_ready(srv.cache)

    run = Run()
    live: Dict[int, _Live] = {}
    steps: List[dict] = []
    compiles0 = ctx.compile_log.n
    tracer = SubTrace(ctx.trace, run, seconds)
    ctx.open_window()
    if mix["mode"] == "open_loop":
        t_open = _open_loop(srv, plan, seconds, live, steps, tracer)
    else:
        t_open = _saturated(srv, gen, int(mix["queue_depth"]), seconds,
                            live, steps, tracer)
    t_end = time.perf_counter()
    tracer.finish()
    compiles = ctx.compile_log.n - compiles0
    run.memory_peak_bytes = memory_peak_bytes()

    t_close = t_open + seconds
    recs = sorted(live.values(), key=lambda r: r.req.uid)
    finished = [r for r in recs if r.req.status == DONE
                and len(r.req.output) == r.req.max_new_tokens]
    run.attempted, run.failed = len(recs), len(recs) - len(finished)
    ttft = [r.stamps[0] - r.due for r in finished]
    gaps = [b - a for r in finished for a, b in zip(r.stamps, r.stamps[1:])]
    run.window_s = seconds
    run.metrics = {
        "ttft_mean_ms": 1e3 * float(np.mean(ttft)),
        "itl_p95_ms": 1e3 * float(np.percentile(gaps, 95)),
        "serve_tokens_per_s": _tokens_in_window(live, t_close) / seconds,
    }
    run.requests = [{"queue_s": r.req.queue_s, "prefill_s": r.req.prefill_s,
                     "ttft_s": t, "prompt": len(r.req.prompt),
                     "first": r.stamps[0] - t_open}
                    for r, t in zip(finished, ttft)]
    run.steps = [dict(s, t0=s["t0"] - t_open, t1=s["t1"] - t_open)
                 for s in steps]
    late = [r.submit - r.due for r in recs]
    run.notes.update({
        "requests_due": len(recs), "served_after_close_s": t_end - t_close,
        "generator_late_ms_p50": 1e3 * float(np.percentile(late, 50)),
        "generator_late_ms_max": 1e3 * max(late),
        "ttft_ms": {f"p{q}": 1e3 * float(np.percentile(ttft, q))
                    for q in (50, 90, 95)},
        "prefill_programs": srv.prefill_programs(), "steps": len(steps),
        "window_compiles": compiles})

    # ---- correctness: free the server, then the reference in blocks
    for a in jax.tree.leaves((srv.params, srv.cache)):
        a.delete()
    del srv, params
    sample = _sample(finished, int(mix["check_requests"]), seed)
    seqs = [(r.req.prompt, np.asarray(r.req.output, np.int32))
            for r in sample]
    run.compared = {"seqs": seqs, "spec": spec,
                    "pad_to": geo["max_len"], "dtype": cfg.param_dtype}
    t_ref = time.perf_counter()
    gaps_ref = dense.served_logit_gaps(
        spec, seed, seqs, pad_to=geo["max_len"],
        dtype=cfg.param_dtype)
    run.notes["reference_s"] = time.perf_counter() - t_ref
    run.compared["gaps"] = gaps_ref
    run.notes["checked_tokens"] = int(sum(len(g) for g in gaps_ref))
    got = gap_readings(gaps_ref)
    lim = run.compared["limits"] = c["limits"]
    run.checks = [Check("window_compiles", compiles, 0)] + [
        Check(k, got[k], lim[k]) for k in sorted(lim)]
    run.notes["not_compared"] = {k: v for k, v in got.items()
                                 if k not in lim}
    return run

