"""Distributed-runtime tests: sharding rules, checkpoint, data pipeline,
fault-tolerant trainer, serving, gradient compression.

Multi-device behaviour is exercised in a subprocess with 8 placeholder host
devices (the parent process must keep its single-device view for the other
tests — jax locks device count at first init)."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.data.pipeline import DataConfig, Prefetcher, SyntheticLM
from repro.train import checkpoint as CKPT

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_subprocess(code: str, devices: int = 8, timeout: int = 420):
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=SRC)
    res = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=timeout,
                         env=env)
    assert res.returncode == 0, f"STDOUT:\n{res.stdout}\nSTDERR:\n{res.stderr}"
    return res.stdout


# ------------------------------------------------------------- data pipeline

def test_data_deterministic_and_resumable():
    cfg = DataConfig(vocab=64, seq_len=16, global_batch=4, seed=3)
    ds = SyntheticLM(cfg)
    b1 = ds.batch_at(7)
    b2 = ds.batch_at(7)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    assert b1["tokens"].shape == (4, 16)
    assert (b1["labels"][:, -1] == -1).all()
    # host sharding partitions the global batch
    parts = []
    for h in range(2):
        dsh = SyntheticLM(DataConfig(vocab=64, seq_len=16, global_batch=4,
                                     seed=3, n_hosts=2, host_id=h))
        parts.append(dsh.batch_at(7)["tokens"])
    np.testing.assert_array_equal(np.concatenate(parts), b1["tokens"])


def test_prefetcher_matches_direct():
    cfg = DataConfig(vocab=64, seq_len=8, global_batch=2, seed=0)
    ds = SyntheticLM(cfg)
    pf = Prefetcher(ds, start_step=5)
    try:
        for step in (5, 6, 7):
            np.testing.assert_array_equal(pf.next()["tokens"],
                                          ds.batch_at(step)["tokens"])
        assert pf.state()["step"] == 8
    finally:
        pf.close()


# --------------------------------------------------------------- checkpoint

def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
            "b": {"c": jnp.asarray([1, 2, 3], jnp.int32)}}
    CKPT.save(str(tmp_path), 5, tree, {"note": "x"})
    target = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                          tree)
    step, out, meta = CKPT.restore(str(tmp_path), target=target)
    assert step == 5 and meta["note"] == "x"
    np.testing.assert_array_equal(np.asarray(out["a"]), np.asarray(tree["a"]))
    np.testing.assert_array_equal(np.asarray(out["b"]["c"]),
                                  np.asarray(tree["b"]["c"]))


def test_checkpoint_corruption_detected(tmp_path):
    tree = {"a": jnp.ones((4,), jnp.float32)}
    d = CKPT.save(str(tmp_path), 1, tree)
    # flip bytes in the data file
    f = os.path.join(d, "data.msgpack.zst")
    blob = bytearray(open(f, "rb").read())
    blob[len(blob) // 2] ^= 0xFF
    open(f, "wb").write(bytes(blob))
    with pytest.raises(Exception):
        CKPT.restore(str(tmp_path),
                     target=jax.tree.map(
                         lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                         tree))


def test_checkpoint_manager_keep_k(tmp_path):
    mgr = CKPT.CheckpointManager(str(tmp_path), keep=2)
    for s in (1, 2, 3, 4):
        mgr.save_sync(s, {"x": jnp.asarray([s])})
    assert CKPT.available_steps(str(tmp_path)) == [3, 4]
    assert mgr.latest_step() == 4


def test_checkpoint_async(tmp_path):
    mgr = CKPT.CheckpointManager(str(tmp_path), keep=3)
    mgr.save_async(7, {"x": jnp.ones((128, 128))})
    mgr.wait()
    assert mgr.latest_step() == 7


# ---------------------------------------------------- trainer fault-tolerance

def test_trainer_loss_decreases_and_survives_faults(tmp_path):
    from repro.configs import get_config
    from repro.launch.mesh import make_host_mesh
    from repro.train.steps import TrainConfig
    from repro.train.trainer import FailureInjector, Trainer, TrainerConfig

    cfg = get_config("smollm-360m", reduced=True)
    mesh = make_host_mesh(1, 1)
    tc = TrainConfig(lr=1e-3, warmup_steps=5, total_steps=60)
    trc = TrainerConfig(steps=40, ckpt_dir=str(tmp_path), ckpt_every=10,
                        log_every=5)
    injector = FailureInjector(crash_at=17, nan_at=26)
    from repro.data.pipeline import DataConfig as DC
    tr = Trainer(cfg, tc, trc, mesh,
                 data_cfg=DC(vocab=cfg.vocab, seq_len=64, global_batch=4,
                             structure=16),
                 injector=injector)
    log = tr.run()
    assert tr.step == 40
    assert len(injector.fired) == 2          # both faults triggered
    rollbacks = [e for e in log if "event" in e]
    assert len(rollbacks) == 2               # both recovered
    losses = [(e["step"], e["loss"]) for e in log if "loss" in e]
    first = np.mean([l for s, l in losses[:2]])
    last = np.mean([l for s, l in losses[-2:]])
    assert last < first, (first, last)       # still learning after recovery


def test_trainer_raises_device_errors_without_rollback(tmp_path):
    """A device or compile error (JaxRuntimeError, a RuntimeError) would
    only fail again after a restore: it raises at once, unretried."""
    from repro.configs import get_config
    from repro.data.pipeline import DataConfig as DC
    from repro.launch.mesh import make_host_mesh
    from repro.train.steps import TrainConfig
    from repro.train.trainer import FailureInjector, Trainer, TrainerConfig

    class DeviceFault(FailureInjector):
        def maybe_fail(self, step, batch):
            if step == 1:
                raise jax.errors.JaxRuntimeError("RESOURCE_EXHAUSTED: hbm")
            return batch

    cfg = get_config("smollm-360m", reduced=True)
    trc = TrainerConfig(steps=3, ckpt_dir=str(tmp_path), ckpt_every=1)
    tr = Trainer(cfg, TrainConfig(lr=1e-3), trc, make_host_mesh(1, 1),
                 data_cfg=DC(vocab=cfg.vocab, seq_len=16, global_batch=2),
                 injector=DeviceFault())
    with pytest.raises(jax.errors.JaxRuntimeError):
        tr.run()
    assert tr.restarts == 0 and tr.step == 1


def test_trainer_restart_resumes_from_checkpoint(tmp_path):
    from repro.configs import get_config
    from repro.launch.mesh import make_host_mesh
    from repro.train.steps import TrainConfig
    from repro.train.trainer import Trainer, TrainerConfig
    from repro.data.pipeline import DataConfig as DC

    cfg = get_config("qwen2-1.5b", reduced=True)
    mesh = make_host_mesh(1, 1)
    tc = TrainConfig(lr=5e-4, warmup_steps=2, total_steps=30)
    dc = DC(vocab=cfg.vocab, seq_len=32, global_batch=2, structure=8)
    trc = TrainerConfig(steps=10, ckpt_dir=str(tmp_path), ckpt_every=5)
    Trainer(cfg, tc, trc, mesh, data_cfg=dc).run()
    # process "restarts": a new Trainer picks up from the final checkpoint
    trc2 = TrainerConfig(steps=16, ckpt_dir=str(tmp_path), ckpt_every=5)
    tr2 = Trainer(cfg, tc, trc2, mesh, data_cfg=dc)
    assert tr2.step == 10                    # resumed, not reinitialized
    tr2.run()
    assert tr2.step == 16


# ------------------------------------------------------------- sharding rules

def test_sharding_rules_multidevice():
    run_subprocess("""
        import jax, numpy as np
        import jax.numpy as jnp
        from repro.configs import get_config
        from repro.launch.mesh import make_host_mesh
        from repro.dist import sharding as SH
        from repro.models import transformer as T
        from jax.sharding import PartitionSpec as P

        mesh = make_host_mesh(2, 4)
        # smollm: 15 heads % 4 != 0 -> wq TP falls back; d_ff shards
        cfg = get_config('smollm-360m')
        ab = T.abstract_params(jax.random.PRNGKey(0), cfg)
        sh = SH.param_shardings(ab, mesh, cfg)
        wq = sh['layers'][0]['mix']['wq'].spec
        wg = sh['layers'][0]['ffn']['w_gate'].spec
        assert wq[-1] == 'model', wq       # 15 heads * 64 = 960 % 4 == 0
        assert wg[-1] == 'model', wg       # d_ff=2560 % 4 == 0
        emb = sh['embed'].spec
        assert emb[0] == 'model', emb      # vocab shards

        # divisibility fallback: 15 heads on model axis -> check fit_axes
        assert SH.fit_axes(15, 'model', mesh) is None
        assert SH.fit_axes(16, 'model', mesh) == 'model'
        assert SH.fit_axes(8, ('pod','data'), mesh) == ('data',) or \\
               SH.fit_axes(8, ('pod','data'), mesh) == 'data'

        # moe EP vs TP fallback
        cfg2 = get_config('mixtral-8x22b')
        ab2 = T.abstract_params(jax.random.PRNGKey(0), cfg2)
        sh2 = SH.param_shardings(ab2, mesh, cfg2)
        spec = sh2['layers'][0]['ffn']['w_gate'].spec
        assert spec[1] == 'model', spec    # 8 experts % 4 == 0 -> EP
        print('sharding rules OK')
        """)


def test_train_step_runs_sharded_multidevice():
    run_subprocess("""
        import jax, numpy as np, jax.numpy as jnp
        from repro.configs import get_config
        from repro.launch.mesh import make_host_mesh
        from repro.train import steps as ST
        from repro.models import transformer as T

        mesh = make_host_mesh(2, 4)
        cfg = get_config('qwen2-1.5b', reduced=True)
        tc = ST.TrainConfig(lr=1e-3)
        jitted, sh = ST.build_sharded_train_step(cfg, tc, mesh)
        opt = ST.make_optimizer(tc)
        with mesh:
            params = jax.jit(lambda r: T.init_params(r, cfg),
                             out_shardings=sh['params'])(jax.random.PRNGKey(0))
            opt_state = jax.jit(opt.init, out_shardings=sh['opt'])(params)
            batch = {'tokens': jnp.zeros((4, 32), jnp.int32),
                     'labels': jnp.ones((4, 32), jnp.int32)}
            fn = jitted(jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), batch))
            p2, o2, m = fn(params, opt_state, batch)
            assert np.isfinite(float(m['loss']))
        print('sharded train step OK', float(m['loss']))
        """)


def test_compressed_allreduce_multidevice():
    run_subprocess("""
        import jax, numpy as np, jax.numpy as jnp, functools
        from repro.launch.mesh import make_host_mesh
        from repro.optim import compression as C
        from repro.optim.adam import Adam

        mesh = make_host_mesh(4, 1)
        # toy quadratic: params converge under compressed DP gradients
        def loss_fn(params, batch):
            pred = batch['x'] @ params['w']
            return jnp.mean((pred - batch['y'])**2), {}

        rng = np.random.default_rng(0)
        w_true = rng.normal(size=(8, 1)).astype(np.float32)
        params = {'w': jnp.zeros((8, 1), jnp.float32)}
        opt = Adam(lr=3e-2)
        opt_state = opt.init(params)
        err = C.init_error_state(params)
        step = C.make_ddp_compressed_step(loss_fn, opt, mesh)
        losses = []
        with mesh:
            for i in range(150):
                x = rng.normal(size=(16, 8)).astype(np.float32)
                y = x @ w_true
                params, opt_state, err, loss = step(
                    params, opt_state, err, {'x': jnp.asarray(x),
                                             'y': jnp.asarray(y)})
                losses.append(float(loss))
        assert losses[-1] < 1e-2 * losses[0], (losses[0], losses[-1])
        print('compressed DP OK', losses[0], '->', losses[-1])
        """)


def test_elastic_restore_across_meshes(tmp_path):
    """Checkpoint written on a 1-device mesh restores onto a 2x4 mesh with
    different shardings (elastic re-scaling)."""
    run_subprocess(f"""
        import jax, numpy as np, jax.numpy as jnp
        from repro.configs import get_config
        from repro.launch.mesh import make_host_mesh
        from repro.dist import sharding as SH
        from repro.models import transformer as T
        from repro.train import checkpoint as CKPT

        cfg = get_config('qwen2-1.5b', reduced=True)
        params = T.init_params(jax.random.PRNGKey(0), cfg)
        CKPT.save({str(tmp_path)!r}, 3, params)

        mesh = make_host_mesh(2, 4)
        ab = T.abstract_params(jax.random.PRNGKey(0), cfg)
        sh = SH.param_shardings(ab, mesh, cfg)
        step, restored, _ = CKPT.restore({str(tmp_path)!r}, target=ab,
                                         shardings=sh)
        assert step == 3
        # values identical, now sharded on the new mesh
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        n_shards = {{len(l.sharding.device_set)
                    for l in jax.tree.leaves(restored)}}
        assert max(n_shards) > 1   # actually distributed
        print('elastic restore OK')
        """)


# ------------------------------------------------------------------ serving

def test_server_continuous_batching():
    from repro.configs import get_config
    from repro.models import transformer as T
    from repro.train.server import Request, Server

    cfg = get_config("qwen2-1.5b", reduced=True).with_(
        dtype=jnp.float32, param_dtype=jnp.float32, remat=False)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    srv = Server(params, cfg, n_slots=2, max_len=64)
    rng = np.random.default_rng(0)
    reqs = [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab, size=5 + 3 * i)
                    .astype(np.int32),
                    max_new_tokens=4 + i) for i in range(5)]
    for r in reqs:
        srv.submit(r)
    done = srv.run_until_drained()
    assert len(done) == 5
    for r in done:
        assert len(r.output) == r.max_new_tokens
    # batched output == standalone decode for one request
    solo = Server(params, cfg, n_slots=1, max_len=64)
    solo.submit(Request(uid=99, prompt=reqs[0].prompt,
                        max_new_tokens=reqs[0].max_new_tokens))
    ref = solo.run_until_drained()[0]
    batched = [r for r in done if r.uid == 0][0]
    assert ref.output == batched.output


def test_recommended_rules_policy():
    """SP policy learned in §Perf: on for pure-attention stacks, off for
    MoE / recurrent mixers."""
    from repro.configs import get_config
    from repro.dist.sharding import ShardingRules
    on = ("qwen2-1.5b", "minitron-4b", "smollm-360m", "qwen1.5-4b",
          "internvl2-26b", "whisper-base")
    off = ("mixtral-8x22b", "moonshot-v1-16b-a3b", "xlstm-1.3b",
           "jamba-1.5-large-398b")
    for a in on:
        assert ShardingRules.recommended(get_config(a)).sequence_parallel, a
    for a in off:
        assert not ShardingRules.recommended(
            get_config(a)).sequence_parallel, a
