"""Unit tests for ``repro.dist.sharding`` on a single-device CPU mesh.

Multi-device placement behaviour is covered by ``tests/test_distributed.py``
(subprocess with 8 placeholder devices); here we pin down the rule *logic*:
the recommended-rules policy across all 10 archs, spec construction and
divisibility fallbacks, and a smoke train step built through the sharded
builders.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_NAMES, get_config
from repro.dist import sharding as SH
from repro.launch.mesh import make_host_mesh
from repro.models import transformer as T
from repro.train import steps as ST

# §Perf policy: SP on for pure-attention stacks, off for MoE / recurrent.
SP_ON = {"qwen2-1.5b", "minitron-4b", "smollm-360m", "qwen1.5-4b",
         "internvl2-26b", "whisper-base"}
SP_OFF = {"mixtral-8x22b", "moonshot-v1-16b-a3b", "xlstm-1.3b",
          "jamba-1.5-large-398b"}


def _axes(entry):
    """One ``PartitionSpec`` entry as a tuple of mesh axes. JAX 0.9 stores
    a one-axis tuple ``("data",)`` as ``"data"``; compare normalised."""
    if entry is None:
        return ()
    return entry if isinstance(entry, tuple) else (entry,)


@pytest.fixture(autouse=True)
def _reset_batch_axes():
    yield
    T.set_batch_axes(None)  # builders mutate module state; keep tests isolated


def test_recommended_rules_all_archs():
    assert SP_ON | SP_OFF == set(ARCH_NAMES)
    for name in ARCH_NAMES:
        rules = SH.ShardingRules.recommended(get_config(name))
        assert rules.sequence_parallel == (name in SP_ON), name
        assert rules.tp_axis == "model"


def test_fit_axes_and_axis_size_single_device():
    mesh = make_host_mesh(1, 1)
    # everything divides a size-1 axis
    assert SH.fit_axes(15, "model", mesh) == "model"
    assert SH.fit_axes(7, ("pod", "data"), mesh) == ("data",)
    # absent axes never appear
    assert SH.fit_axes(8, "pod", mesh) is None
    assert SH.fit_axes(8, None, mesh) is None
    assert SH.axis_size(mesh, "model") == 1
    assert SH.axis_size(mesh, None) == 1
    assert SH.axis_size(mesh, ("data", "model")) == 1
    assert SH.data_axes(mesh) == ("data",)


@pytest.mark.parametrize("arch", ["smollm-360m", "mixtral-8x22b",
                                  "jamba-1.5-large-398b", "whisper-base"])
def test_param_shardings_valid_namedshardings(arch):
    mesh = make_host_mesh(1, 1)
    cfg = get_config(arch, reduced=True)
    ab = T.abstract_params(jax.random.PRNGKey(0), cfg)
    sh = SH.param_shardings(ab, mesh, cfg)
    flat_ab = jax.tree.leaves(ab)
    flat_sh = jax.tree.leaves(
        sh, is_leaf=lambda x: isinstance(x, NamedSharding))
    assert len(flat_ab) == len(flat_sh) and flat_sh
    for s in flat_sh:
        assert isinstance(s, NamedSharding)
    SH.validate_shardings(ab, sh)  # every spec'd dim divides its axes


def test_param_shardings_layout_rules():
    """Spec shapes on a 1-device mesh (axes of size 1 always fit)."""
    mesh = make_host_mesh(1, 1)
    cfg = get_config("smollm-360m", reduced=True)
    ab = T.abstract_params(jax.random.PRNGKey(0), cfg)
    sh = SH.param_shardings(ab, mesh, cfg)
    assert sh["embed"].spec[0] == "model"             # vocab rows
    assert sh["lm_head"].spec[1] == "model"           # vocab cols
    mix = sh["layers"][0]["mix"]
    assert mix["wq"].spec[-1] == "model"              # column parallel
    assert mix["wo"].spec[1] == "model"               # row parallel (stacked)
    assert mix["wo"].spec[0] is None                  # stack dim never shards
    ffn = sh["layers"][0]["ffn"]
    assert ffn["w_gate"].spec[-1] == "model"
    assert ffn["w_down"].spec[1] == "model"
    assert all(a is None for a in sh["final_ln"].spec)  # norms replicated


def test_moe_expert_parallel_dim():
    mesh = make_host_mesh(1, 1)
    cfg = get_config("mixtral-8x22b", reduced=True)
    ab = T.abstract_params(jax.random.PRNGKey(0), cfg)
    sh = SH.param_shardings(ab, mesh, cfg)
    ffn = sh["layers"][0]["ffn"]
    # stacked MoE weights are (repeats, experts, ...) -> expert dim shards
    assert ffn["w_gate"].spec[1] == "model"
    assert ffn["w_down"].spec[1] == "model"
    assert all(a is None for a in ffn["router"].spec)


def test_fsdp_rules_shard_remaining_dim():
    mesh = make_host_mesh(1, 1)
    cfg = get_config("smollm-360m")  # full size so leaves clear fsdp_min_size
    ab = T.abstract_params(jax.random.PRNGKey(0), cfg)
    sh = SH.param_shardings(ab, mesh, cfg,
                            SH.ShardingRules(fsdp_weights=True))
    wq = sh["layers"][0]["mix"]["wq"].spec
    assert wq[-1] == "model" and _axes(wq[1]) == ("data",)  # TP + ZeRO-3
    SH.validate_shardings(ab, sh)


def test_batch_specs_and_batch_sharding():
    mesh = make_host_mesh(1, 1)
    batch = {"tokens": jax.ShapeDtypeStruct((4, 16), jnp.int32),
             "labels": jax.ShapeDtypeStruct((4, 16), jnp.int32),
             "patches": jax.ShapeDtypeStruct((4, 8, 32), jnp.bfloat16)}
    sh = SH.batch_specs(batch, mesh)
    for k, s in sh.items():
        assert isinstance(s, NamedSharding), k
        assert _axes(s.spec[0]) == ("data",), k
        assert all(a is None for a in s.spec[1:]), k
    tok = SH.batch_sharding(mesh, 4, 1)
    assert tok.spec == P(("data",))


def test_cache_shardings_batch_and_kv_dims():
    mesh = make_host_mesh(1, 1)
    cfg = get_config("qwen2-1.5b", reduced=True)
    cache = jax.eval_shape(lambda: T.init_cache(cfg, 2, 32))
    sh = SH.cache_shardings(cache, mesh, cfg)
    assert _axes(sh["pos"].spec[0]) == ("data",)
    entry = sh["layers"][0]
    assert _axes(entry["k"].spec[1]) == ("data",)    # batch dim
    assert entry["k"].spec[3] == "model"      # kv-head dim
    assert entry["k"].spec[2] is None         # cache seq never sharded
    SH.validate_shardings(cache, sh)


def test_param_bytes_per_device_counts_shards():
    mesh = make_host_mesh(1, 1)
    cfg = get_config("qwen2-1.5b", reduced=True)
    ab = T.abstract_params(jax.random.PRNGKey(0), cfg)
    sh = SH.param_shardings(ab, mesh, cfg)
    total = sum(int(np.prod(l.shape)) * jnp.dtype(l.dtype).itemsize
                for l in jax.tree.leaves(ab))
    # 1-device mesh: every "shard" is the whole array
    assert SH.param_bytes_per_device(ab, sh) == total


def test_build_sharded_train_step_smoke():
    """One real optimization step through the sharded builders on CPU."""
    mesh = make_host_mesh(1, 1)
    cfg = get_config("smollm-360m", reduced=True)
    tc = ST.TrainConfig(lr=1e-3, warmup_steps=2, total_steps=10)
    jitted, sh = ST.build_sharded_train_step(cfg, tc, mesh)
    opt = ST.make_optimizer(tc)
    with mesh:
        params = jax.jit(lambda r: T.init_params(r, cfg),
                         out_shardings=sh["params"])(jax.random.PRNGKey(0))
        opt_state = jax.jit(opt.init, out_shardings=sh["opt"])(params)
        batch = {"tokens": jnp.zeros((2, 32), jnp.int32),
                 "labels": jnp.ones((2, 32), jnp.int32)}
        # snapshot before the call: the jit donates the params buffers
        before = [np.asarray(l, np.float32) for l in jax.tree.leaves(params)]
        fn = jitted(jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), batch))
        p2, o2, metrics = fn(params, opt_state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert float(metrics["grad_norm"]) > 0.0
    # params actually moved
    deltas = [float(np.max(np.abs(a - np.asarray(b, np.float32))))
              for a, b in zip(before, jax.tree.leaves(p2))]
    assert max(deltas) > 0.0


def test_sequence_parallel_rules_smoke():
    """SP rules lower and run on a 1-device mesh (seq divisor 1)."""
    mesh = make_host_mesh(1, 1)
    cfg = get_config("qwen2-1.5b", reduced=True)
    rules = SH.ShardingRules(sequence_parallel=True)
    tc = ST.TrainConfig(lr=1e-3)
    jitted, sh = ST.build_sharded_train_step(cfg, tc, mesh, rules=rules)
    opt = ST.make_optimizer(tc)
    with mesh:
        params = jax.jit(lambda r: T.init_params(r, cfg),
                         out_shardings=sh["params"])(jax.random.PRNGKey(1))
        opt_state = jax.jit(opt.init, out_shardings=sh["opt"])(params)
        batch = {"tokens": jnp.zeros((2, 16), jnp.int32),
                 "labels": jnp.ones((2, 16), jnp.int32)}
        fn = jitted(jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), batch))
        _, _, metrics = fn(params, opt_state, batch)
    assert np.isfinite(float(metrics["loss"]))
