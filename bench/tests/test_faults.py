"""A whole run of each cell at reduced sizes on the CPU, with the look for
a chip skipped: sound, it is correct; with the timed path broken
underneath, ``correct`` comes out false. One test per fault the cell can
have (there is no exchange between chips on one chip)."""
import jax
import jax.numpy as jnp
import pytest

from bench.tests.harness import correct, run_tiny
from repro.models import transformer as T
from repro.train import server as server_mod
from repro.train import trainer as trainer_mod

CHAT, TRAIN = "qwen2-1.5b.chat", "smollm-360m.train"


def _patch_decode(monkeypatch, make):
    """Replace each new Server's decode program by ``make(cfg)``."""
    init = server_mod.Server.__init__

    def patched(self, params, cfg, *a, **k):
        init(self, params, cfg, *a, **k)
        self._decode = make(cfg)

    monkeypatch.setattr(server_mod.Server, "__init__", patched)


@pytest.mark.parametrize("name", [CHAT, TRAIN])
def test_sound_run_is_correct(name):
    run = run_tiny(name)
    assert correct(run), run.checks
    assert run.failed == 0 and run.attempted > 0


def test_serve_state_left_unchanged(monkeypatch):
    """The decode step returns its cache as it came in."""
    _patch_decode(monkeypatch, lambda cfg: jax.jit(
        lambda p, c, t: (T.decode_step(p, c, t, cfg)[0], c)))
    assert not correct(run_tiny(CHAT))


def test_serve_half_batch_left_out(monkeypatch):
    """The second half of the slots gets no logits of its own."""
    def make(cfg):
        def step(p, c, t):
            logits, c = T.decode_step(p, c, t, cfg)
            return logits.at[logits.shape[0] // 2:].set(0.0), c
        return jax.jit(step, donate_argnums=(1,))
    _patch_decode(monkeypatch, make)
    assert not correct(run_tiny(CHAT))


def test_serve_token_altered(monkeypatch):
    """Every fourth decode step ranks the token after the best first."""
    def make(cfg):
        calls = {"n": 0}
        inner = jax.jit(lambda p, c, t: T.decode_step(p, c, t, cfg),
                        donate_argnums=(1,))

        def step(p, c, t):
            logits, c = inner(p, c, t)
            calls["n"] += 1
            if calls["n"] % 4 == 0:
                nxt = (jnp.argmax(logits, -1) + 1) % logits.shape[-1]
                logits = logits.at[jnp.arange(logits.shape[0]), nxt].set(
                    1e9)
            return logits, c
        return step
    _patch_decode(monkeypatch, make)
    assert not correct(run_tiny(CHAT))


def _patch_train_step(monkeypatch, wrap):
    orig = trainer_mod.train_step_fn
    monkeypatch.setattr(trainer_mod, "train_step_fn",
                        lambda cfg, tc: wrap(orig(cfg, tc)))


def test_train_state_left_unchanged(monkeypatch):
    """The step computes, then hands back the state it was given."""
    def wrap(step):
        def f(params, opt, batch):
            _, _, metrics = step(params, opt, batch)
            return params, opt, metrics
        return f
    _patch_train_step(monkeypatch, wrap)
    run = run_tiny(TRAIN)
    assert not correct(run)
    assert {k.name: k.ok for k in run.checks}["update_norm_gap"] is False


def test_train_half_batch_left_out(monkeypatch):
    """The step's mean is over the first half of the rows only."""
    def wrap(step):
        def f(params, opt, batch):
            half = jax.tree.map(lambda x: x[: x.shape[0] // 2], batch)
            return step(params, opt, half)
        return f
    _patch_train_step(monkeypatch, wrap)
    assert not correct(run_tiny(TRAIN))
