"""Serving launcher: batched continuous decoding of synthetic requests.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --reduced \
        --requests 8 --slots 4

Every request is submitted up front and the server runs until drained;
``violation_pct`` is the share of finished requests whose end-to-end
latency exceeds ``--sla-ms``.  Open-loop serving (timed arrivals against
the wall clock) is the benchmark's: ``python3 bench/run.py`` and
``bench/sweep.py``.

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
                    "requests")
    ap.add_argument("--arch", choices=ARCH_NAMES, default="qwen2-1.5b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--sla-ms", type=float, default=500.0,
                    help="end-to-end latency SLA in milliseconds; "
                         "violation_pct counts requests over it")
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
    args = make_parser().parse_args(argv)
    enable_compile_cache()
    cfg, srv = build_server(args)
    doc = {"arch": cfg.name, "sla_ms": args.sla_ms}
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
