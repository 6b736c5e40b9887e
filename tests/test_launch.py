"""The serve and train launchers' reusable parts, and the compile-cache
helper they call, at reduced size on the CPU."""
import json
import math

import jax
import numpy as np
import pytest

from repro.configs import get_config
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


@pytest.fixture
def _no_repo_cache(monkeypatch, tmp_path, _restore_cache_dir):
    """``serve.main`` enables the compile cache; point it off the repo."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))


def _serve_main(tmp_path, capsys, *argv):
    path = tmp_path / "serve.json"
    serve.main(["--reduced", "--slots", "2", "--max-new", "2",
                "--json-out", str(path), *argv])
    return json.loads(path.read_text()), capsys.readouterr().out


@pytest.mark.parametrize("arch", ["qwen2-1.5b", "moonlight-16b-a3b"])
def test_serve_main_drains_and_reports(arch, tmp_path, capsys,
                                       _no_repo_cache):
    doc, out = _serve_main(tmp_path, capsys, "--arch", arch,
                           "--requests", "3")
    assert doc["arch"] == get_config(arch, reduced=True).name
    assert doc["requests"] == 3 and doc["generated_tokens"] == 3 * 2
    assert doc["rejected"] == doc["abandoned"] == 0
    assert 0.0 <= doc["violation_pct"] <= 100.0
    assert "WARNING" not in out


def test_serve_main_reports_rejected_loudly(tmp_path, capsys,
                                            _no_repo_cache):
    n, max_len = 8, 16
    # the prompt lengths serve_drain draws from --seed 0
    rng = np.random.default_rng(0)
    vocab = get_config("qwen2-1.5b", reduced=True).vocab
    lens = []
    for _ in range(n):
        lens.append(int(rng.integers(4, 24)))
        rng.integers(0, vocab, size=lens[-1])
    too_long = sum(x >= max_len for x in lens)
    assert 0 < too_long < n
    doc, out = _serve_main(tmp_path, capsys, "--requests", str(n),
                           "--max-len", str(max_len))
    assert doc["rejected"] == too_long and doc["abandoned"] == 0
    assert doc["requests"] == n - too_long
    assert f"WARNING: {too_long} request(s) rejected:" in out


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
