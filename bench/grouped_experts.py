"""The held experts' SwiGLU on one chip, three ways: ``jax.lax.ragged_dot``,
megablox ``gmm`` at several tilings (rows padded to the row tile), and
(up to 128 tokens) every held expert on every token, masked by a routing
weight. Shapes are Moonlight-16B-A3B's share of one chip: 8 experts of
2048 x 1408, top-6 of 64, so one pair in eight falls on a held expert.
One JSON line per token count, milliseconds per call:

    python3 bench/grouped_experts.py

This is what ``repro.models.moe`` chose its grouped product and tiles by
(``PERF.md`` §6). On the CPU ``gmm`` runs in interpret mode, slowly, and
the times say nothing of a chip.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--tokens", default="32,128,1024,3072")
    ap.add_argument("--iters", type=int, default=30)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    from repro.kernels import interpret_mode

    def bench(f, *a):
        jax.block_until_ready(f(*a))
        t = time.perf_counter()
        for _ in range(args.iters):
            out = f(*a)
        jax.block_until_ready(out)
        return round(1e3 * (time.perf_counter() - t) / args.iters, 4)

    held, d, f, k, n_experts = 8, 2048, 1408, 6, 64
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 3)
    wg = jax.random.normal(ks[0], (held, d, f), jnp.bfloat16) * 0.02
    wu = jax.random.normal(ks[1], (held, d, f), jnp.bfloat16) * 0.02
    wd = jax.random.normal(ks[2], (held, f, d), jnp.bfloat16) * 0.02

    def ragged(x, s):
        dot = lambda a, w: jax.lax.ragged_dot(
            a, w, s, preferred_element_type=jnp.float32)
        return dot((jax.nn.silu(dot(x, wg)) * dot(x, wu)).astype(x.dtype), wd)

    def grouped(x, s, tm, tk, tn):
        m = x.shape[0]
        xp = jnp.pad(x, ((0, -m % tm), (0, 0)))
        mm = lambda a, w, t: gmm(a, w, s, jnp.float32, tiling=t,
                                 interpret=interpret_mode())
        h = jax.nn.silu(mm(xp, wg, (tm, tk, f))) * mm(xp, wu, (tm, tk, f))
        return mm(h.astype(x.dtype), wd, (tm, f, tn))[:m]

    def masked(x, gate):
        up = lambda w: jnp.einsum("td,edf->tef", x, w,
                                  preferred_element_type=jnp.float32)
        a = jax.nn.silu(up(wg)) * up(wu) * gate[..., None]
        return jnp.einsum("tef,efd->td", a.astype(x.dtype), wd,
                          preferred_element_type=jnp.float32)

    for tokens in (int(t) for t in args.tokens.split(",")):
        rows = tokens * k
        x = jax.random.normal(key, (rows, d), jnp.bfloat16)
        sizes = jnp.asarray(np.full(held, rows // n_experts), jnp.int32)
        res = {"tokens": tokens, "rows": rows, "in_groups": int(sizes.sum()),
               "ragged_dot": bench(jax.jit(ragged), x, sizes)}
        for tm, tk, tn in ((64, 512, 512), (128, 512, 512), (128, 1024, 1024),
                           (256, 512, 512), (512, 512, 512)):
            res[f"gmm_{tm}_{tk}_{tn}"] = bench(
                jax.jit(lambda x, s, t=(tm, tk, tn): grouped(x, s, *t)),
                x, sizes)
        if tokens <= 128:
            xt = jax.random.normal(key, (tokens, d), jnp.bfloat16)
            gate = jax.random.uniform(key, (tokens, held))
            res["masked_every_held"] = bench(jax.jit(masked), xt, gate)
        print(json.dumps(res), flush=True)


if __name__ == "__main__":
    main()
