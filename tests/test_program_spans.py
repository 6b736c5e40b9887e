"""``repro.obs`` spans on the serve and train paths, the tracer's
``annotate`` hook, and the names the model's programs and scopes give the
compiled code.

One reduced model and one ``Server`` for the module: every test drains it
before returning."""
import contextlib
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs import get_config
from repro.models import transformer as T
from repro.train.server import Request, Server

SCOPES = ("embed", "attention", "kv_update", "mlp", "lm_head")
STEP_CHILDREN = ("serve.admit", "serve.best_effort", "serve.decode",
                 "serve.decode_sync", "serve.emit")
ADMIT_CHILDREN = ["serve.prefill", "serve.insert", "serve.first_token"]
TRAIN_CHILDREN = ["train.data", "train.dispatch", "train.loss_sync",
                  "train.ckpt"]


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("qwen2-1.5b", reduced=True).with_(
        dtype=jnp.float32, param_dtype=jnp.float32, remat=False)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


@pytest.fixture(scope="module")
def srv(setup):
    cfg, params = setup
    return Server(params, cfg, n_slots=3, max_len=64)


def _requests(cfg, uid0=0):
    """Two waves: three arrivals that share a step's admission, then one
    alone."""
    rng = np.random.default_rng(uid0)
    return ([[Request(uid=uid0 + i, prompt=rng.integers(
        0, cfg.vocab, 5 + 3 * i).astype(np.int32), max_new_tokens=4 + i)
        for i in range(3)]],
        [[Request(uid=uid0 + 3, prompt=rng.integers(
            0, cfg.vocab, 7).astype(np.int32), max_new_tokens=3)]])


def _serve(srv, waves):
    out = {}
    for wave in waves:
        for batch in wave:
            for r in batch:
                srv.submit(r)
        for r in srv.run_until_drained():
            out[r.uid] = list(r.output)
    return out


def _tree(spans):
    """(span, children) for every span, children in start order; a span's
    parent is the innermost span one level up that holds it."""
    spans = sorted(spans, key=lambda s: (s["t"], -s["dur"]))
    kids = {id(s): [] for s in spans}
    for s in spans:
        holders = [p for p in spans if p["depth"] == s["depth"] - 1
                   and p["t"] <= s["t"]
                   and s["t"] + s["dur"] <= p["t"] + p["dur"]]
        if holders:
            kids[id(max(holders, key=lambda p: p["t"]))].append(s)
    return [(s, kids[id(s)]) for s in spans]


def test_server_span_tree_and_counters(setup, srv):
    cfg, _ = setup
    tracer = obs.Tracer()
    with obs.use(tracer):
        _serve(srv, _requests(cfg))
    tree = _tree(tracer.spans())
    steps = [(s, k) for s, k in tree if s["name"] == "serve.step"]
    assert steps and all(s["depth"] == 0 for s, _ in steps)
    admits_per_step = []
    for _, kids in steps:
        names = [k["name"] for k in kids]
        # admissions first, then best effort, then decode, sync, emit
        order = [STEP_CHILDREN.index(n) for n in names]
        assert order == sorted(order)
        assert names.count("serve.best_effort") == 1
        admits_per_step.append(names.count("serve.admit"))
        if "serve.decode" in names:
            assert names[-3:] == ["serve.decode", "serve.decode_sync",
                                  "serve.emit"]
    admits = [(s, k) for s, k in tree if s["name"] == "serve.admit"]
    assert sorted(s["args"]["uid"] for s, _ in admits) == [0, 1, 2, 3]
    for s, kids in admits:
        assert [k["name"] for k in kids] == ADMIT_CHILDREN
        assert s["args"]["prompt_len"] in (5, 8, 11, 7)
    decodes = [s for s, _ in tree if s["name"] == "serve.decode"]
    for s in decodes:
        assert 1 <= s["args"]["active"] <= 3
        assert s["args"]["context"] > 5 * s["args"]["active"] - 1
    # admissions, and those that shared a step, are counted from the spans
    assert sum(admits_per_step) == 4
    assert sum(n for n in admits_per_step if n >= 2) == 3
    assert len(decodes) == sum(1 for _, k in steps
                               if "serve.decode" in [c["name"] for c in k])
    # the step keeps no counters or gauges of its own
    assert tracer.metrics.snapshot() == {
        "counters": {}, "gauges": {}, "histograms": {}}


def test_server_noop_records_nothing_and_serves_the_same_tokens(setup, srv):
    cfg, _ = setup
    tracer = obs.Tracer()
    with obs.use(tracer):
        traced = _serve(srv, _requests(cfg, uid0=10))
    assert obs.current() is obs.NOOP
    plain = _serve(srv, _requests(cfg, uid0=10))
    assert plain == traced and len(plain) == 4
    assert obs.NOOP.recent_spans() == [] and obs.NOOP.metrics.snapshot() == {}


class _Recorder:
    """An ``annotate`` hook that logs entries and exits."""

    def __init__(self):
        self.log = []

    def __call__(self, name, **args):
        rec = self

        class _Ann:
            def __enter__(self):
                rec.log.append(("enter", name, args))

            def __exit__(self, *exc):
                rec.log.append(("exit", name, {}))

        return _Ann()


def test_annotate_hook_sees_the_same_names_and_nesting(setup, srv):
    cfg, _ = setup
    rec = _Recorder()
    tracer = obs.Tracer(annotate=rec)
    with obs.use(tracer):
        _serve(srv, _requests(cfg, uid0=20))
    spans = sorted(tracer.spans(), key=lambda s: (s["t"], -s["dur"]))
    depth, entered = 0, []
    for kind, name, args in rec.log:
        if kind == "enter":
            entered.append((name, depth, args))
            depth += 1
        else:
            depth -= 1
    assert depth == 0
    assert [(n, d) for n, d, _ in entered] == [
        (s["name"], s["depth"]) for s in spans]
    assert [a for n, _, a in entered if n == "serve.admit"] == [
        s["args"] for s in spans if s["name"] == "serve.admit"]


def test_annotation_exits_when_the_span_body_raises():
    rec = _Recorder()
    tracer = obs.Tracer(annotate=rec)
    with pytest.raises(KeyError):
        with tracer.span("outer", k=1):
            with tracer.span("inner"):
                raise KeyError("x")
    assert [(k, n) for k, n, _ in rec.log] == [
        ("enter", "outer"), ("enter", "inner"), ("exit", "inner"),
        ("exit", "outer")]
    assert rec.log[0][2] == {"k": 1}
    assert [s["name"] for s in tracer.spans()] == ["inner", "outer"]


def _trainer(tmp_path, steps, injector=None):
    from repro.data.pipeline import DataConfig
    from repro.launch.mesh import make_host_mesh
    from repro.train.steps import TrainConfig
    from repro.train.trainer import Trainer, TrainerConfig
    cfg = get_config("smollm-360m", reduced=True)
    return Trainer(cfg, TrainConfig(lr=1e-3),
                   TrainerConfig(steps=steps, ckpt_dir=str(tmp_path),
                                 ckpt_every=1, log_every=1),
                   make_host_mesh(1, 1),
                   data_cfg=DataConfig(vocab=cfg.vocab, seq_len=16,
                                       global_batch=2),
                   injector=injector)


def test_trainer_step_spans_and_counters(tmp_path):
    tr = _trainer(tmp_path, steps=3)
    tracer = obs.Tracer()
    with obs.use(tracer):
        tr.run()
    tree = _tree(tracer.spans())
    steps = [(s, k) for s, k in tree if s["name"] == "train.step"]
    assert [s["args"]["step"] for s, _ in steps] == [0, 1, 2]
    for s, kids in steps:
        assert s["depth"] == 0
        assert [k["name"] for k in kids] == TRAIN_CHILDREN
    # the step-0 anchor and the final save sit outside the steps
    assert [s["name"] for s, _ in tree if s["depth"] == 0] == (
        ["train.ckpt"] + ["train.step"] * 3 + ["train.ckpt"])
    assert tracer.metrics.snapshot() == {
        "counters": {}, "gauges": {}, "histograms": {}}


def test_trainer_rollback_reruns_the_step_span(tmp_path):
    from repro.train.trainer import FailureInjector
    tr = _trainer(tmp_path, steps=3, injector=FailureInjector(crash_at=1))
    tracer = obs.Tracer()
    with obs.use(tracer):
        log = tr.run()
    assert [e["step"] for e in log if "event" in e] == [1]
    # the failed step's span closed as the exception left it, with no
    # children after the failed hand-over; the step then ran again
    tree = _tree(tracer.spans())
    steps = [(s, k) for s, k in tree if s["name"] == "train.step"]
    assert [s["args"]["step"] for s, _ in steps] == [0, 1, 1, 2]
    assert [k["name"] for k in steps[1][1]] == ["train.data"]
    assert [k["name"] for k in steps[2][1]] == TRAIN_CHILDREN
    assert [e for e in tracer.events() if e["ph"] == "i"] == []


def test_trainer_annotate_hook_sees_the_train_spans(tmp_path):
    tr = _trainer(tmp_path, steps=2)
    rec = _Recorder()
    tracer = obs.Tracer(annotate=rec)
    with obs.use(tracer):
        tr.run()
    depth, entered = 0, []
    for kind, name, args in rec.log:
        if kind == "enter":
            entered.append((name, depth, args))
            depth += 1
        else:
            depth -= 1
    assert depth == 0
    spans = sorted(tracer.spans(), key=lambda s: (s["t"], -s["dur"]))
    assert [(n, d) for n, d, _ in entered] == [
        (s["name"], s["depth"]) for s in spans]
    assert [a for n, _, a in entered if n == "train.step"] == [
        {"step": 0}, {"step": 1}]


_OP = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*"
                 r'op_name="([^"]*)"', re.M)


def _scopes(hlo_text):
    """The innermost named scope of every instruction's ``op_name``."""
    out = {}
    for name, op_name in _OP.findall(hlo_text):
        found = re.findall(r"\b(" + "|".join(SCOPES) + r")\b", op_name)
        if found:
            out[name] = (found[-1], op_name)
    return out


def test_programs_are_named_and_ops_map_to_model_scopes(setup, srv):
    from repro.train.steps import TrainConfig, make_optimizer, train_step_fn
    cfg, params = setup
    decode = srv._decode.lower(params, srv.cache,
                               jnp.asarray(srv.last_tok)).compile()
    prefill = srv._prefill.lower(
        params, {"tokens": jnp.zeros((1, 9), jnp.int32)}).compile()
    tc = TrainConfig()
    batch = {"tokens": jnp.zeros((2, 16), jnp.int32),
             "labels": jnp.zeros((2, 16), jnp.int32)}
    train = jax.jit(train_step_fn(cfg, tc)).lower(
        params, make_optimizer(tc).init(params), batch).compile()
    for prog, name in ((decode, "serve_decode"), (prefill, "serve_prefill"),
                       (train, "train_step")):
        assert re.search(rf"HloModule jit_{name}\b", prog.as_text())
    dec = _scopes(decode.as_text())
    assert {s for s, _ in dec.values()} == set(SCOPES)
    # the cache write sits inside attention
    assert all("attention/kv_update" in o for s, o in dec.values()
               if s == "kv_update")
    # the token's cache write is one scatter per stacked leaf, in
    # kv_update; the layer scan neither stacks nor copies a leaf, since it
    # carries the cache and each layer reads its K/V by index
    stacked = ",".join(map(str, srv.cache["layers"][0]["k"].shape))
    ops = re.findall(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*\w+\[([\d,]*)\]"
                     r"\S*\s+([\w\-]+)\(", decode.as_text(), re.M)
    scatters = [(n, dims) for n, dims, op in ops if op == "scatter"]
    assert len(scatters) == 2
    assert all(dec[n][0] == "kv_update" and dims == stacked
               for n, dims in scatters)
    assert not [n for n, dims, op in ops
                if op in ("dynamic-update-slice", "copy") and dims == stacked]
    assert {s for s, _ in _scopes(prefill.as_text()).values()} == set(SCOPES)
    assert {"embed", "attention", "mlp", "lm_head"} <= {
        s for s, _ in _scopes(train.as_text()).values()}


def _compiled(cfg, params, program, named_scope):
    """The compiled text of one program of a fresh ``Server`` (or the train
    step) with ``jax.named_scope`` as given, metadata stripped."""
    from repro.train.steps import TrainConfig, make_optimizer, train_step_fn
    saved = jax.named_scope
    jax.named_scope = named_scope
    try:
        if program == "train_step":
            tc = TrainConfig()
            batch = {"tokens": jnp.zeros((2, 16), jnp.int32),
                     "labels": jnp.zeros((2, 16), jnp.int32)}
            low = jax.jit(train_step_fn(cfg, tc)).lower(
                params, make_optimizer(tc).init(params), batch)
        else:
            s = Server(params, cfg, n_slots=3, max_len=64)
            low = (s._decode.lower(params, s.cache, jnp.asarray(s.last_tok))
                   if program == "serve_decode" else s._prefill.lower(
                       params, {"tokens": jnp.zeros((1, 9), jnp.int32)}))
        text = low.compile().as_text()
    finally:
        jax.named_scope = saved
    # drop the source tables (file, function, line) and each op's metadata,
    # then name every instruction and computation by its first appearance,
    # since two traces of the same code may number them differently
    text = re.sub(r"^(\d+ .*|[A-Z][A-Za-z]+)\n", "", text, flags=re.M)
    text = re.sub(r",?\s*metadata=\{[^}]*\}", "", text)
    names = {}
    return re.sub(r"%[\w.\-]+",
                  lambda m: names.setdefault(m.group(), f"%v{len(names)}"),
                  text)


@pytest.mark.parametrize("program", ["serve_decode", "serve_prefill",
                                     "train_step"])
def test_scopes_change_metadata_only(setup, program):
    cfg, params = setup
    scoped = _compiled(cfg, params, program, jax.named_scope)
    plain = _compiled(cfg, params, program,
                      lambda name: contextlib.nullcontext())
    assert scoped == plain


def test_obs_imports_without_jax():
    """Workers and daemons import ``repro.obs`` and must never pay for
    JAX: a fresh interpreter that imports it and uses the annotate hook
    has no ``jax`` module loaded."""
    code = ("import sys, contextlib\n"
            "from repro import obs\n"
            "t = obs.Tracer(annotate=lambda n, **a: contextlib.nullcontext())\n"
            "with obs.use(t), obs.current().span('s', x=1):\n"
            "    pass\n"
            "assert len(t.spans()) == 1\n"
            "print('jax' in sys.modules)\n")
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env = dict(os.environ, PYTHONPATH=src)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "False"
