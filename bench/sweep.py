"""The knee of an open-loop serving mix, found once on the chip:

    python3 bench/sweep.py --workload qwen2-1.5b.chat --seconds 30 \\
        --rates 5,5.5,6,6.6,7.3,8 --seed 5

One server is built and warmed once; the mix is then offered at each rate
in turn for ``--seconds``, and served until it drains. For each rate one
JSON line gives the backlog (requests due and not yet admitted) at each
quarter of the window, the time the server needed after the close to
finish, and the tails. The knee is the highest rate whose backlog does not
grow over the window. The benchmark's own runs never run this; the rate it
finds goes into the traffic file by hand.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import numpy as np  # noqa: E402

from bench import program, run as bench_run, traffic  # noqa: E402
from bench.drivers import serve  # noqa: E402


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    c = bench_run.cell(args.workload)
    bench_run.device_info(c["workload"]["chips"])
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    cfg = program.arch_config(c["config"])
    geo = c["config"]["serve"]
    params = program.make_params(c["config"], cfg, args.seed)
    srv = serve.Server(params, cfg,
                       n_slots=geo["slots"], max_len=geo["max_len"])
    every_length = traffic.quantile_lengths(c["mix"]["prompt"], 4096)
    serve._warm(srv, sorted(set(every_length.tolist())), cfg.vocab)
    for rate in map(float, args.rates.split(",")):
        mix = dict(c["mix"], rate_per_s=rate)
        plan = traffic.open_loop(mix, args.seed, args.seconds, cfg.vocab)
        live, steps = {}, []
        t_open = serve._open_loop(srv, plan, args.seconds, live, steps)
        t_end = time.perf_counter()
        recs = list(live.values())
        backlog = []
        for q in (0.25, 0.5, 0.75, 1.0):
            t = t_open + q * args.seconds
            backlog.append(sum(1 for r in recs if r.due <= t and (
                not r.stamps or r.stamps[0] > t)))
        ttft = [r.stamps[0] - r.due for r in recs if r.stamps]
        gaps = [b - a for r in recs for a, b in zip(r.stamps, r.stamps[1:])]
        print(json.dumps({
            "rate_per_s": rate, "requests": len(recs),
            "backlog_at_quarters": backlog,
            "drain_after_close_s": t_end - t_open - args.seconds,
            "ttft_p50_ms": 1e3 * float(np.percentile(ttft, 50)),
            "ttft_p95_ms": 1e3 * float(np.percentile(ttft, 95)),
            "itl_p50_ms": 1e3 * float(np.percentile(gaps, 50)),
            "itl_p95_ms": 1e3 * float(np.percentile(gaps, 95)),
            "max_active": max((s["decoded"] for s in steps), default=0)}),
            flush=True)


if __name__ == "__main__":
    main()
