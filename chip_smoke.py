"""Bring-up smoke run of the system's main paths on one TPU chip.

    python chip_smoke.py               # one chip: tune+deploy, serve, train
    python chip_smoke.py --four-chips  # four chips: sharded training only

Phases, in order, each through the entry points a user calls:

1. device check: the first device must be a TPU; there is no CPU fallback;
2. tune and deploy (the paper's path, as in ``examples/quickstart.py``): an
   ARCO session (MAPPO + GBT on the chip) over ResNet-18's conv2a layer,
   the winning knobs deployed through the Pallas GEMM (``ops.
   conv2d_from_knobs``), lowered to a Mosaic kernel and checked against
   ``ref.conv2d_ref``;
3. serve: ``qwen2-1.5b`` at published widths and full depth, built and
   drained by ``repro.launch.serve``; every request must finish, and
   teacher-forced decode logits must match prefill logits;
4. train: ``smollm-360m`` at published widths and full depth for a few
   steps through ``Trainer`` on a 1x1 mesh (``repro.launch.train``).

``--four-chips`` runs only ``smollm-360m`` training on a data=2 x model=2
mesh and compares its losses with the same seed and batches on one device.

Weights and data are random, made from fixed seeds. Any failed check raises
and the script exits non-zero. Lines tagged ``[observation]`` are bring-up
observations (wall and compile seconds, cache hits, peak device memory), not
benchmark metrics. The last line of standard output is one JSON object
naming the device.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.compiler import Session, TuningTask  # noqa: E402
from repro.core.tuner import TunerConfig  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.launch import serve, train  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.train.server import DONE, _insert_slot  # noqa: E402

TUNE_LAYER = "resnet-18:conv2a"      # 56x56x64 -> 64, 3x3: 4 of 17 layers
TUNE_BUDGET = 32
SERVE_ARGV = ["--arch", "qwen2-1.5b", "--slots", "4", "--requests", "8",
              "--max-new", "16", "--max-len", "128"]
PROMPT_LENS = (16, 32)
TRAIN_ARGV = ["--arch", "smollm-360m", "--steps", "4", "--batch", "8",
              "--seq", "256"]
FOUR_CHIP_ARGV = ["--arch", "smollm-360m", "--steps", "3", "--batch", "8",
                  "--seq", "256", "--lr", "1e-3"]
# Decode vs prefill, bf16: both paths round every layer's activations to
# bf16 (unit roundoff u = 2**-8), in a different order (one token vs the
# whole prompt per matmul, cached vs fresh K/V), so the two logit vectors
# differ by rounding that grows slowly with depth: 0.015-0.017 of the
# largest logit at 28 layers of widths 64-256 on the CPU. A wrong position,
# mask or cache slot gives an O(1) difference. The bound is 0.05 of the
# largest prefill logit.
DECODE_REL_TOL = 0.05
# One device vs a data=2 x model=2 mesh, same seed and batches: the sharded
# matmuls and the gradient all-reduce sum bf16 values in another order, so
# the loss, a mean over 2048 tokens, moves by rounding only (1e-5 to 1e-4
# relative at reduced width on 4 CPU devices). The first step sees equal
# weights; later steps also carry the updates' rounding.
FIRST_LOSS_REL_TOL = 2e-3
LOSS_REL_TOL = 5e-3


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def observe(msg: str) -> None:
    print(f"[observation] {msg}", flush=True)


class CompileLog:
    """Backend compile seconds and persistent-cache hits, from JAX's own
    monitoring events (a compile served by the cache counts as a hit)."""

    def __init__(self):
        self.secs, self.n, self.hits, self.misses = 0.0, 0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += secs
            self.n += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return self.secs, self.n, self.hits, self.misses


def peak_bytes() -> str:
    stats = jax.devices()[0].memory_stats()
    if not stats or "peak_bytes_in_use" not in stats:
        return "not reported"
    return f"{stats['peak_bytes_in_use']} B"


def device_check(want: int) -> dict:
    devs = jax.devices()
    d = devs[0]
    print(f"device: platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)}", flush=True)
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke: the first JAX device is {d.platform!r}"
                         f", not a TPU; this script does not fall back")
    if len(devs) < want:
        raise SystemExit(f"chip_smoke: needs {want} TPU devices, "
                         f"found {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


# ----------------------------------------------------------------- phases

def phase_tune(layer: str = TUNE_LAYER, budget: int = TUNE_BUDGET) -> None:
    task = next(t for t in TuningTask.conv_tasks("resnet-18")
                if t.name == layer)
    rep = Session(task, tuner=TunerConfig.fast(), budget=budget,
                  seed=0).run().single
    knobs = rep.best_settings
    print(f"tune: {layer} {task.space.workload} -> {knobs} after "
          f"{rep.n_measurements} measurements (modelled latency "
          f"{rep.best_latency:.3e} s, analytical oracle)", flush=True)

    wl = task.space.workload
    kx, kw = jax.random.split(jax.random.PRNGKey(0))
    # bf16-representable float32 inputs: every product is exact in float32
    # whatever number of MXU passes either side uses, so the kernel and the
    # reference differ only in the order of their float32 sums.
    x = jax.random.normal(kx, (wl["b"], wl["h"], wl["w"], wl["ci"]),
                          jnp.bfloat16).astype(jnp.float32)
    w = jax.random.normal(kw, (wl["kh"], wl["kw"], wl["ci"], wl["co"]),
                          jnp.bfloat16).astype(jnp.float32)

    def deploy(x, w):
        return ops.conv2d_from_knobs(x, w, wl["stride"], wl["pad"], **knobs)

    hlo = jax.jit(deploy).lower(x, w).compile().as_text()
    out = deploy(x, w)
    with jax.default_matmul_precision("highest"):
        want = ref.conv2d_ref(x, w, wl["stride"], wl["pad"])
        mag = ref.conv2d_ref(jnp.abs(x), jnp.abs(w), wl["stride"], wl["pad"])
    # Each side's float32 sum of K exact products is within
    # gamma_K * sum|x*w| of the exact value (gamma_K = K*u / (1 - K*u),
    # u = 2**-24), so the two differ by at most twice that.
    k = wl["kh"] * wl["kw"] * wl["ci"]
    gamma = k * 2.0 ** -24 / (1 - k * 2.0 ** -24)
    err = np.asarray(jnp.abs(out - want))
    bound = 2 * gamma * np.asarray(mag)
    print(f"tune: deployed conv max|err| {err.max():.3e}, bound "
          f"{bound.max():.3e}, worst err/bound {(err / bound).max():.3f}",
          flush=True)
    check(out.shape == want.shape, f"deployed shape {out.shape} != "
          f"{want.shape}")
    check(bool(np.isfinite(np.asarray(out)).all()), "non-finite conv output")
    check(bool((err <= bound).all()), "deployed conv disagrees with "
          "ref.conv2d_ref beyond the float32 summation bound")
    check("tpu_custom_call" in hlo, "the deployed GEMM did not lower to a "
          "Mosaic kernel (no tpu_custom_call in the compiled program)")


def phase_serve(argv=SERVE_ARGV, prompt_lens=PROMPT_LENS) -> None:
    args = serve.make_parser().parse_args(argv)
    cfg, srv = serve.build_server(args)
    n = T.param_count(srv.params)
    kv_per_token = sum(2 * e["k"][:, 0, 0].nbytes    # K and V, all layers
                       for e in srv.cache["layers"] if "k" in e)
    print(f"serve: {cfg.name} {n / 1e9:.3f}B params, "
          f"{n * jnp.dtype(cfg.param_dtype).itemsize / 1e9:.2f} GB weights, "
          f"KV cache {kv_per_token} B per token per slot, "
          f"{args.slots} slots x {args.max_len}", flush=True)
    doc, done = serve.serve_drain(srv, cfg, args, prompt_lens=prompt_lens)
    print(f"serve: drain {json.dumps(doc)}", flush=True)
    print(f"serve: {srv.prefill_programs()} prefill programs compiled "
          f"(warm-up + prompt lengths {list(prompt_lens)})", flush=True)
    check(len(done) == args.requests
          and all(r.status == DONE for r in done),
          f"not every request ended done: "
          f"{[(r.uid, r.status) for r in done]}")
    check(not srv.rejected and not srv.abandoned,
          f"{len(srv.rejected)} rejected, {len(srv.abandoned)} abandoned")
    check(all(len(r.output) == args.max_new for r in done),
          "a request stopped short of --max-new tokens")

    # Teacher forcing: prefill the prompt minus its last token, put that
    # cache in slot 0 of the server's batched cache, decode the last token
    # with the server's decode program, and compare with prefill logits of
    # the whole prompt (tests/test_models.py does the same in float32).
    rng = np.random.default_rng(args.seed + 1)
    prompt = rng.integers(0, cfg.vocab, size=prompt_lens[-1]).astype(np.int32)
    toks = jnp.asarray(prompt[None, :])
    full, _ = srv._prefill(srv.params, {"tokens": toks})
    _, part = srv._prefill(srv.params, {"tokens": toks[:, :-1]})
    cache = _insert_slot(T.init_cache(cfg, args.slots, args.max_len), part, 0)
    last = np.zeros((args.slots, 1), np.int32)
    last[0, 0] = prompt[-1]
    dec, _ = srv._decode(srv.params, cache, jnp.asarray(last))
    a = np.asarray(dec[0], np.float32)
    b = np.asarray(full[0], np.float32)
    check(bool(np.isfinite(a).all() and np.isfinite(b).all()),
          "non-finite serve logits")
    rel = float(np.abs(a - b).max() / np.abs(b).max())
    print(f"serve: teacher-forced decode vs prefill max|dlogit| / "
          f"max|logit| = {rel:.3e} (bound {DECODE_REL_TOL}), argmax "
          f"{int(a.argmax())} vs {int(b.argmax())}", flush=True)
    check(rel <= DECODE_REL_TOL, "decode logits disagree with prefill")


def _train(argv):
    with tempfile.TemporaryDirectory(prefix="chip_smoke_ckpt_") as ckpt:
        args = train.make_parser().parse_args([*argv, "--ckpt", ckpt])
        trainer, doc = train.run(args)
    losses = [e["loss"] for e in trainer.metrics_log if "loss" in e]
    steps = [e.get("sec") for e in trainer.metrics_log if "loss" in e]
    print(f"train: {json.dumps(doc)}", flush=True)
    observe(f"train {dict(trainer.mesh.shape)}: seconds per step "
            f"(first includes compilation) {steps}")
    check(trainer.restarts == 0, f"{trainer.restarts} rollbacks")
    check(len(losses) == args.steps and all(map(math.isfinite, losses)),
          f"losses {losses}")
    return trainer, losses


def phase_train(argv=TRAIN_ARGV) -> None:
    _, losses = _train(argv)
    print(f"train: losses {losses}", flush=True)


def phase_four_chips(argv=FOUR_CHIP_ARGV) -> None:
    one = _train([*argv, "--data", "1", "--model", "1"])[1]
    trainer, four = _train([*argv, "--data", "2", "--model", "2"])
    leaves = jax.tree.leaves((trainer.params, trainer.opt_state))
    devs = {len(x.sharding.device_set) for x in leaves}
    check(devs == {4}, f"state leaves span {devs} devices, not all 4")
    check(any(not x.sharding.is_fully_replicated for x in leaves),
          "no state leaf is sharded")
    rel = [abs(a - b) / abs(a) for a, b in zip(one, four)]
    print(f"four-chips: losses 1 device {one}", flush=True)
    print(f"four-chips: losses 2x2 mesh {four}", flush=True)
    print(f"four-chips: relative difference {rel} (bounds "
          f"{FIRST_LOSS_REL_TOL} first step, {LOSS_REL_TOL} later)",
          flush=True)
    check(rel[0] <= FIRST_LOSS_REL_TOL, "first-step losses disagree")
    check(max(rel) <= LOSS_REL_TOL, "losses disagree")


# ------------------------------------------------------------------- main

def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="only sharded training on a 2x2 mesh of 4 chips, "
                         "compared with one device")
    args = ap.parse_args(argv)

    device = device_check(4 if args.four_chips else 1)
    cache_dir = enable_compile_cache()
    observe(f"compilation cache at {cache_dir}")
    log = CompileLog()
    phases = ([("four-chips", phase_four_chips)] if args.four_chips else
              [("tune", phase_tune), ("serve", phase_serve),
               ("train", phase_train)])
    for name, fn in phases:
        t0, c0 = time.perf_counter(), log.snapshot()
        try:
            fn()
        except BaseException:
            print(f"phase {name}: FAILED", file=sys.stderr, flush=True)
            raise
        c1 = log.snapshot()
        observe(f"{name}: wall {time.perf_counter() - t0:.2f} s, backend "
                f"compile {c1[0] - c0[0]:.2f} s in {c1[1] - c0[1]} programs, "
                f"cache hits {c1[2] - c0[2]} misses {c1[3] - c0[3]}, "
                f"peak device memory so far {peak_bytes()}")
        print(f"phase {name}: ok", flush=True)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
