"""Serving launcher: batched continuous decoding, optionally with an
online tuning session measuring candidate ShardSpace geometries on idle
decode slots (``--autotune``, see :mod:`repro.compiler.serve_tune`).

    # plain serving of synthetic requests
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --reduced \
        --requests 8 --slots 4

    # timed Poisson arrivals + online tuning under a 500 ms p99 SLA
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --reduced \
        --requests 64 --rate 20 --autotune --budget 24 --sla-ms 500

``--rate 0`` (default) submits every request up front — the drain-the-batch
mode the launcher always had.  With ``--rate`` the trace replays Poisson
arrivals against the wall clock (idle gaps fast-forwarded), which is what
gives ``--autotune`` idle windows to measure in.

Throughput excludes jit warm-up: one throwaway request is served before
the timed run so the first-step compile doesn't pollute ``tokens_per_sec``.
Rejected and abandoned requests are reported loudly and never averaged
into latency stats (their latency fields are None by design).
"""
from __future__ import annotations

import argparse
import json
import time
from typing import List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.configs import ARCH_NAMES, get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer as T
from repro.train.server import Request, Server


def _latency_stats(done) -> dict:
    if not done:
        return {"mean_latency_s": None, "p50_latency_s": None,
                "p99_latency_s": None, "mean_queue_s": None,
                "mean_prefill_s": None, "mean_decode_s": None}
    lats = np.asarray([r.latency_s for r in done])
    return {
        "mean_latency_s": round(float(lats.mean()), 4),
        "p50_latency_s": round(float(np.percentile(lats, 50)), 4),
        "p99_latency_s": round(float(np.percentile(lats, 99)), 4),
        "mean_queue_s": round(float(np.mean(
            [r.queue_s for r in done])), 4),
        "mean_prefill_s": round(float(np.mean(
            [r.prefill_s for r in done])), 4),
        "mean_decode_s": round(float(np.mean(
            [r.decode_s for r in done])), 4),
    }


def _warm_up(srv: Server, vocab: int) -> None:
    """Serve one throwaway request so the jit compiles of prefill/decode
    land outside the timed run."""
    srv.submit(Request(uid=-1, prompt=np.arange(4, dtype=np.int32) % vocab,
                       max_new_tokens=2))
    srv.run_until_drained(max_steps=64)


def make_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        description="continuous-batching LM server over synthetic "
                    "requests, with optional online geometry tuning")
    ap.add_argument("--arch", choices=ARCH_NAMES, default="qwen2-1.5b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rate", type=float, default=0.0, metavar="REQ_PER_S",
                    help="Poisson arrival rate; 0 = submit everything up "
                         "front (legacy drain mode)")
    ap.add_argument("--autotune", action="store_true",
                    help="run an online tuning session on idle decode "
                         "slots while serving (needs --rate > 0)")
    ap.add_argument("--budget", type=int, default=24,
                    help="measurements per tuned cell (--autotune)")
    ap.add_argument("--sla-ms", type=float, default=500.0,
                    help="p99 end-to-end latency SLA in milliseconds")
    ap.add_argument("--records", metavar="PATH", default=None,
                    help="JSONL measurement records for warm resume "
                         "(--autotune)")
    ap.add_argument("--monitor", type=int, default=None, metavar="PORT",
                    help="live /metrics + /status + /trace on this port "
                         "for the duration of the run (0 = ephemeral)")
    ap.add_argument("--json-out", metavar="PATH", default=None,
                    help="also write the report JSON here")
    return ap


def build_server(args) -> Tuple[T.ArchConfig, Server]:
    """Random weights from ``--seed``, a ``Server`` over them, warmed up."""
    cfg = get_config(args.arch, reduced=args.reduced)
    params = T.init_params(jax.random.PRNGKey(args.seed), cfg)
    srv = Server(params, cfg, n_slots=args.slots, max_len=args.max_len)
    _warm_up(srv, cfg.vocab)
    return cfg, srv


def serve_drain(srv: Server, cfg: T.ArchConfig, args,
                prompt_lens: Optional[Sequence[int]] = None
                ) -> Tuple[dict, List[Request]]:
    """Submit ``--requests`` requests up front and serve until drained.
    Prompt lengths are drawn from 4..23, or cycle through ``prompt_lens``
    (a fixed set bounds the prefill programs compiled, one per length).
    Returns the report fields and the finished requests."""
    rng = np.random.default_rng(args.seed)
    t0 = time.perf_counter()
    for i in range(args.requests):
        n = (prompt_lens[i % len(prompt_lens)] if prompt_lens
             else int(rng.integers(4, 24)))
        srv.submit(Request(
            uid=i,
            prompt=rng.integers(0, cfg.vocab, size=n).astype(np.int32),
            max_new_tokens=args.max_new))
    done = srv.run_until_drained()
    dt = time.perf_counter() - t0
    toks = sum(len(r.output) for r in done)
    doc = {
        "requests": len(done),
        "generated_tokens": toks,
        "wall_s": round(dt, 3),
        "tokens_per_sec": round(toks / dt, 1),
        "rejected": len(srv.rejected),
        "abandoned": len(srv.abandoned),
    }
    doc.update(_latency_stats(done))
    if done:
        lats = np.asarray([r.latency_s for r in done])
        doc["violation_pct"] = round(float(
            100.0 * (lats > args.sla_ms / 1e3).mean()), 3)
    return doc, done


def main(argv: Optional[Sequence[str]] = None):
    ap = make_parser()
    args = ap.parse_args(argv)
    if args.autotune and args.rate <= 0:
        ap.error("--autotune needs --rate > 0: tuning measures in the "
                 "idle gaps between arrivals, and a fully up-front queue "
                 "has none")

    enable_compile_cache()
    cfg, srv = build_server(args)

    doc = {"arch": cfg.name, "sla_ms": args.sla_ms}
    if args.autotune or args.rate > 0:
        from repro.compiler.serve_tune import (LiveServeHost, ServeModel,
                                               ServeSLA, TraceConfig,
                                               tune_while_serving)
        trace = TraceConfig(
            n_requests=args.requests, rate_per_s=args.rate,
            prompt_len=(4, max(args.max_len // 4, 5)),
            max_new=(2, args.max_new), seed=args.seed)
        host = LiveServeHost(
            srv, trace, sla=ServeSLA(target_s=args.sla_ms / 1e3),
            model=ServeModel(arch=args.arch), vocab=cfg.vocab,
            seed=args.seed)
        if args.autotune:
            rep = tune_while_serving(
                host, budget=args.budget, records=args.records,
                monitor=args.monitor, seed=args.seed,
                offline_compare=False)
            doc["autotune"] = {
                "budget": rep.budget,
                "online": rep.online,
                "measurements": rep.serve["measurements"],
                "preempted": rep.serve["preempted"],
            }
        else:
            host.finish_serving()
        summary = host.summary()
        done = host.done
        doc.update({
            "requests": summary["served"],
            "generated_tokens": int(sum(len(r.output) for r in done)),
            "wall_s": round(summary["sim_time_s"], 3),
            "tokens_per_sec": round(summary["tokens_per_sec"] or 0.0, 1),
            "violation_pct": round(summary["violation_pct"] or 0.0, 3),
            "rejected": summary["rejected"],
            "abandoned": summary["abandoned"],
        })
        doc.update(_latency_stats(done))
    else:
        doc.update(serve_drain(srv, cfg, args)[0])

    # loud, unmissable: these were never served and are NOT in the stats
    for kind, reqs in (("rejected", srv.rejected),
                       ("abandoned", srv.abandoned)):
        if reqs:
            print(f"WARNING: {len(reqs)} request(s) {kind}:")
            for r in reqs[:5]:
                print(f"  uid={r.uid} status={r.status} "
                      f"error={r.error or '-'}")
            if len(reqs) > 5:
                print(f"  ... and {len(reqs) - 5} more")

    out = json.dumps(doc, indent=1)
    print(out)
    if args.json_out:
        with open(args.json_out, "w") as f:
            f.write(out + "\n")


if __name__ == "__main__":
    main()
