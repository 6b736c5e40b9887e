"""The serve and train launchers' reusable parts, and the compile-cache
helper they call, at reduced size on the CPU."""
import math

import jax
import pytest

from repro.launch import compile_cache, serve, train
from repro.train.server import DONE


def test_train_launcher_runs_reduced(tmp_path):
    args = train.make_parser().parse_args(
        ["--arch", "smollm-360m", "--reduced", "--steps", "3",
         "--batch", "4", "--seq", "32", "--ckpt", str(tmp_path)])
    trainer, doc = train.run(args)
    assert doc["steps"] == 3 and trainer.restarts == 0
    losses = [e["loss"] for e in trainer.metrics_log if "loss" in e]
    assert len(losses) == 3 and all(map(math.isfinite, losses))


def test_serve_drain_fixed_prompt_lengths_bound_prefill_programs():
    args = serve.make_parser().parse_args(
        ["--arch", "qwen2-1.5b", "--reduced", "--slots", "2",
         "--requests", "5", "--max-new", "3", "--max-len", "32"])
    cfg, srv = serve.build_server(args)
    assert srv.prefill_programs() == 1                 # the warm-up prompt
    doc, done = serve.serve_drain(srv, cfg, args, prompt_lens=(6, 9))
    assert doc["requests"] == 5 and doc["rejected"] == doc["abandoned"] == 0
    assert sorted(len(r.prompt) for r in done) == [6, 6, 6, 9, 9]
    assert all(r.status == DONE and len(r.output) == 3 for r in done)
    assert srv.prefill_programs() == 3                 # warm-up + 2 lengths


@pytest.fixture
def _restore_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_compile_cache_defaults_to_fixed_repo_path(monkeypatch,
                                                    _restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == compile_cache.REPO_CACHE_DIR
    assert path.endswith(".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path


def test_compile_cache_leaves_env_setting_to_jax(monkeypatch, tmp_path,
                                                 _restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
