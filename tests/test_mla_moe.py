"""Moonlight-16B-A3B's block (latent attention, a leading dense layer,
sigmoid-routed dropless experts with shared ones and an expert share)
against the plain reference in ``bench/reference/mla_moe.py``, at the
reduced size on the CPU with seeded random weights.

Every comparison runs both sides in float32, so the tolerances hold only
the differences of summation order (chunked online softmax, the absorbed
decode, grouped products) and never a lower precision: the served
bfloat16 weights would miss them by orders of magnitude.
"""
import os
import sys
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench.reference import mla_moe as R  # noqa: E402
from repro import obs  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.models import layers as L  # noqa: E402
from repro.models import moe as MOE  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.train.server import Request, Server  # noqa: E402

F32 = jnp.float32
# float32 on both sides: the logits (of size ~1-5) agree to the rounding of
# sums taken in another order, ~1e-5 observed; 1e-4 leaves room for that
# and none for a lost term or a lower precision
TOL = dict(rtol=1e-4, atol=1e-4)


def _cfg(**kw):
    return get_config("moonlight-16b-a3b", reduced=True).with_(
        dtype=F32, param_dtype=F32, remat=False, **kw)


def _model(cfg, seed=7):
    spec = R.spec_of({}, cfg)
    key = R.seed_key(seed)
    ref = jax.jit(partial(R.init_params, spec=spec, dtype=F32))(key)
    return spec, key, R.to_program(ref)


def _tokens(n, s, vocab, seed=1):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), (n, s),
                                         0, vocab))


def test_reduced_preset_keeps_the_structure():
    cfg = get_config("moonlight-16b-a3b", reduced=True)
    assert cfg.pattern == (("mla", "moe"),) and cfg.n_dense_lead == 1
    assert cfg.n_shared_experts >= 1 and cfg.moe_impl == "dropless"
    assert 0 < cfg.held_experts < cfg.n_experts
    full = get_config("moonlight-16b-a3b")
    assert (full.n_layers, full.kv_lora_rank, full.qk_rope_head_dim,
            full.n_experts, full.moe_top_k, full.rms_eps) == (
        27, 512, 64, 64, 6, 1e-5)


# 2 x 32 tokens run every held expert on every token, 2 x 72 the sorted
# pairs through the grouped products (MOE.ROW_TILE is 128)
@pytest.mark.parametrize("seq", [32, 72])
def test_prefill_logits_match_the_reference(seq):
    cfg = _cfg()
    spec, key, params = _model(cfg)
    toks = _tokens(2, seq, cfg.vocab)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(R.logits(key, toks, spec, F32))
        batch = {"tokens": jnp.asarray(toks)}
        h = jax.jit(lambda p, b: T.hidden_states(p, b, cfg)[0])(params,
                                                               batch)
        every = np.asarray(jnp.einsum("bsd,dv->bsv", h, params["lm_head"]))
        last, _, _ = jax.jit(lambda p, b: T.prefill(p, b, cfg, 96))(params,
                                                                    batch)
    np.testing.assert_allclose(every, want, **TOL)
    np.testing.assert_allclose(np.asarray(last), want[:, -1], **TOL)


def test_prefill_then_decode_through_the_latent_cache():
    """Prefill 20 tokens, then decode 12 one at a time through the latent
    cache (absorbed attention): each step's logits are the reference's
    full forward at that position."""
    cfg = _cfg()
    spec, key, params = _model(cfg, seed=11)
    toks = _tokens(2, 32, cfg.vocab, seed=2)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(R.logits(key, toks, spec, F32))
        lg, cache, _ = jax.jit(lambda p, b: T.prefill(p, b, cfg, 40))(
            params, {"tokens": jnp.asarray(toks[:, :20])})
        step = jax.jit(lambda p, c, t: T.decode_step(p, c, t, cfg))
        got = [np.asarray(lg)]
        for i in range(20, 32):
            lg, cache, _ = step(params, cache,
                                jnp.asarray(toks[:, i:i + 1]))
            got.append(np.asarray(lg))
    assert set(cache["layers"][0]) == {"c", "kr"}
    assert cache["lead"][0]["c"].shape == (1, 2, 40, cfg.kv_lora_rank)
    np.testing.assert_allclose(np.stack(got, 1), want[:, 19:], **TOL)


def _moe_layer(spec, key, i=1):
    """Layer ``i``'s MoE weights: the reference's and the program's."""
    w = jax.tree.map(np.asarray, R.init_layer(key, i, spec, F32, True))
    p = {"ln": w["mlp_norm"], "router": w["router"],
         "router_bias": w["router_bias"], "w_gate": w["expert_gate"],
         "w_up": w["expert_up"], "w_down": w["expert_down"],
         "shared": {"w_gate": w["shared_gate"], "w_up": w["shared_up"],
                    "w_down": w["shared_down"]}}
    return w, p


def test_routing_matches_the_reference_and_the_bias_decides():
    cfg = _cfg()
    spec, key, _ = _model(cfg)
    w, p = _moe_layer(spec, key)
    h = np.asarray(jax.random.normal(jax.random.PRNGKey(3), (256, 64)))
    with jax.default_matmul_precision("highest"):
        ids, wt = MOE.route(jnp.asarray(h), p, cfg)
        rid, rwt = R.route(jnp.asarray(h), w, spec)
    order, rorder = np.argsort(ids, -1), np.argsort(rid, -1)
    np.testing.assert_array_equal(np.take_along_axis(np.asarray(ids), order,
                                                     -1),
                                  np.take_along_axis(np.asarray(rid), rorder,
                                                     -1))
    np.testing.assert_allclose(np.take_along_axis(np.asarray(wt), order, -1),
                               np.take_along_axis(np.asarray(rwt), rorder,
                                                  -1), rtol=1e-6, atol=1e-6)
    # weights: normalised scores times the published scale
    np.testing.assert_allclose(np.asarray(wt).sum(-1), cfg.routed_scale,
                               rtol=1e-6)
    # the seeded selection bias changes some choices (not all)
    unbiased, _ = MOE.route(jnp.asarray(h), dict(p, router_bias=np.zeros_like(
        p["router_bias"])), cfg)
    moved = (np.sort(unbiased, -1) != np.sort(ids, -1)).any(-1)
    assert 0 < moved.mean() < 1


@pytest.mark.parametrize("impl", ["dense", "dropping"])
@pytest.mark.parametrize("field", [dict(n_shared_experts=1),
                                   dict(routed_scale=2.0),
                                   dict(n_held_experts=4, first_expert=4)])
def test_the_other_moe_paths_refuse_the_dropless_fields(impl, field):
    """Shared experts, a routed scale and an expert share exist only in
    the ``dropless`` path; the others would ignore them, so a
    configuration that sets one is refused."""
    kw = dict(n_shared_experts=0, routed_scale=1.0, n_held_experts=0,
              first_expert=0)
    with pytest.raises(ValueError, match="dropless"):
        get_config("moonlight-16b-a3b", reduced=True).with_(
            moe_impl=impl, **{**kw, **field})
    get_config("moonlight-16b-a3b", reduced=True).with_(moe_impl=impl, **kw)


def _moe_ref(x, w, spec):
    with jax.default_matmul_precision("highest"):
        return np.asarray(R.moe(jnp.asarray(x), w, spec, "f32"))


def _moe_prog(x, p, cfg):
    with jax.default_matmul_precision("highest"):
        y, n = MOE.moe_dropless(jnp.asarray(x), p, cfg)
    return np.asarray(y), np.asarray(n)


# tokens per row: 2 x 24 < MOE.ROW_TILE run the masked path, 2 x 80 the
# sorted grouped products
@pytest.mark.parametrize("tokens", [24, 80])
def test_dropless_when_every_token_picks_the_same_experts(tokens):
    """A bias that sends every token to the same held experts: nothing is
    dropped, the layer is the reference's, and the counters see it."""
    cfg = _cfg()
    spec, key, _ = _model(cfg)
    w, p = _moe_layer(spec, key)
    bias = np.zeros(cfg.n_experts, np.float32)
    bias[:cfg.moe_top_k] = 10.0              # experts 0..k-1, all held
    w, p = dict(w, router_bias=bias), dict(p, router_bias=bias)
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(4),
                                     (2, tokens, 64)))
    y, n = _moe_prog(x, p, cfg)
    np.testing.assert_allclose(y, _moe_ref(x, w, spec), **TOL)
    np.testing.assert_array_equal(n, [2 * tokens * cfg.moe_top_k,
                                      cfg.moe_top_k, 2 * tokens])


@pytest.mark.parametrize("tokens", [24, 80])
def test_expert_shares_add_up_to_the_uncut_layer(tokens):
    """Expert parallelism without its exchange: disjoint shares covering
    every expert, each computing its own experts' part, with the shared
    experts and the residual counted once, add up to the uncut reference
    layer."""
    cfg = _cfg(n_held_experts=0)                 # uncut: holds all 8
    spec, key, _ = _model(cfg)
    w, p = _moe_layer(spec, key)
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(5),
                                     (2, tokens, 64)))
    want = _moe_ref(x, w, spec)
    routed = np.zeros_like(x)
    for first, held in ((0, 2), (2, 2), (4, 4)):
        cut = cfg.with_(n_held_experts=held, first_expert=first)
        spec_cut = R.spec_of({}, cut)
        w_cut = jax.tree.map(np.asarray,
                             R.init_layer(key, 1, spec_cut, F32, True))
        # a share's seeded experts are the uncut model's slice
        np.testing.assert_array_equal(w_cut["expert_up"],
                                      w["expert_up"][first:first + held])
        mine = {k: v[first:first + held] if k.startswith("w_") else v
                for k, v in p.items() if k != "shared"}
        routed += _moe_prog(x, mine, cut)[0] - x
    with jax.default_matmul_precision("highest"):
        h = L.rmsnorm(jnp.asarray(x), p["ln"], cfg.rms_eps)
        shared = np.asarray(MOE._swiglu(h, p["shared"]))
    np.testing.assert_allclose(x + routed + shared, want, **TOL)


def test_counters_of_a_hand_counted_routing():
    """The router reads one input dimension per expert, and each token is
    built to choose the experts listed for it: the pairs on held experts,
    the held experts hit and the largest load are counted by hand."""
    cfg = _cfg()                                 # holds experts 0-3 of 8
    d, e, k = cfg.d_model, cfg.n_experts, cfg.moe_top_k
    chosen = [(0, 1, 2), (0, 5, 6), (4, 5, 6), (1, 2, 7), (0, 3, 4),
              (0, 1, 7)]
    x = np.zeros((1, len(chosen), d), np.float32)
    for t, ids in enumerate(chosen):
        x[0, t, list(ids)] = 3.0
        x[0, t, e:] = 0.5                        # the rest of the row
    router = np.zeros((d, e), np.float32)
    router[np.arange(e), np.arange(e)] = 4.0
    f = cfg.expert_d_ff
    rng = np.random.default_rng(0)
    p = {"ln": np.ones(d, np.float32), "router": router,
         "router_bias": np.zeros(e, np.float32),
         "w_gate": rng.normal(size=(4, d, f)).astype(np.float32),
         "w_up": rng.normal(size=(4, d, f)).astype(np.float32),
         "w_down": rng.normal(size=(4, f, d)).astype(np.float32)}
    ids, _ = MOE.route(jnp.asarray(x[0]), p, cfg)
    assert [tuple(sorted(r)) for r in np.asarray(ids).tolist()] == chosen
    held = [[i for i in ids_t if i < 4] for ids_t in chosen]
    pairs = sum(len(h) for h in held)                              # 10
    hit = len({i for h in held for i in h})                        # 4
    load = max(sum(i in h for h in held) for i in range(4))        # 4 (0)
    assert k == 3 and (pairs, hit, load) == (10, 4, 4)
    _, n = _moe_prog(x, p, cfg)
    np.testing.assert_array_equal(n, [pairs, hit, load])


def test_server_records_the_counters_only_when_traced():
    """Served through the normal ``Server``: the prefill and decode
    programs hand back the counters, which a traced step adds to its
    ``serve.prefill`` / ``serve.decode`` spans; the outputs are the same
    traced or not."""
    cfg = get_config("moonlight-16b-a3b", reduced=True)
    params = T.init_params(jax.random.PRNGKey(0), cfg)

    def serve(tracer):
        srv = Server(params, cfg, n_slots=2, max_len=32)
        with obs.use(tracer):
            for i in range(3):
                srv.submit(Request(uid=i, prompt=np.arange(
                    i, 6 + i, dtype=np.int32), max_new_tokens=3))
            return {r.uid: r.output for r in srv.run_until_drained()}

    tr = obs.Tracer()
    assert serve(tr) == serve(obs.NOOP)
    spans = {n: [e.get("args", {}) for e in tr.spans() if e["name"] == n]
             for n in ("serve.prefill", "serve.decode")}
    assert len(spans["serve.prefill"]) == 3 and spans["serve.decode"]
    n_moe = cfg.n_layers - cfg.n_dense_lead
    for args in spans["serve.prefill"] + spans["serve.decode"]:
        assert set(args) >= {f"moe_{c}" for c in MOE.COUNTERS}
        assert 0 < args["moe_experts_hit"] <= n_moe * cfg.held_experts
        assert args["moe_max_load"] <= args["moe_pairs"]
    # a decode step computes every slot: 2 tokens, at most k pairs each
    for args in spans["serve.decode"]:
        assert args["moe_pairs"] <= 2 * cfg.moe_top_k * n_moe


@pytest.mark.parametrize("arch", ["qwen2-1.5b", "smollm-360m"])
def test_dense_programs_return_no_counters(arch):
    cfg = get_config(arch, reduced=True)
    assert not cfg.counts_moe
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    out = T.prefill(params, {"tokens": jnp.zeros((1, 8), jnp.int32)}, cfg,
                    16)
    assert len(out) == 2
