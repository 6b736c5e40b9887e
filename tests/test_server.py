"""Serving-stack regression tests: latency accounting, graceful
rejection, termination modes, slot reuse, interleaved-admission parity,
and abandoned-request marking — the serving bugfixes of the online-tuning
PR, pinned down — and the jitted slot insert (``serve_insert``) against
the eager ``_insert_slot`` it wraps, on a dense and a latent-attention
MoE config.

One reduced model + one shared jitted decode function for the whole
module (every ``Server`` re-jitting its own decode would dominate the
suite's wall clock)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import transformer as T
from repro.train.server import (ABANDONED, DONE, QUEUED, REJECTED, Request,
                                Server, _insert_slot)


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("qwen2-1.5b", reduced=True).with_(
        dtype=jnp.float32, param_dtype=jnp.float32, remat=False)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    decode = jax.jit(lambda p, c, t: T.decode_step(p, c, t, cfg),
                     donate_argnums=(1,))
    return cfg, params, decode


@pytest.fixture(scope="module")
def srv(setup):
    """One shared server — every test drains it before returning."""
    cfg, params, decode = setup
    return Server(params, cfg, n_slots=2, max_len=64, decode_fn=decode)


def _prompt(cfg, n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, cfg.vocab, size=n).astype(np.int32)


def test_latency_breakdown_and_slot_reuse(setup, srv):
    cfg, _, _ = setup
    reqs = [Request(uid=i, prompt=_prompt(cfg, 5 + 2 * i, seed=i),
                    max_new_tokens=4) for i in range(5)]
    for r in reqs:
        srv.submit(r)
        assert r.status == QUEUED and r.submit_s is not None
    done = srv.run_until_drained()
    # 5 requests through 2 slots: slots were freed and reused mid-batch
    assert len(done) == 5 and not srv.abandoned
    assert sorted(srv.free) == [0, 1] and not srv.active
    for r in done:
        assert r.status == DONE and r.ok
        assert len(r.output) == r.max_new_tokens
        # end-to-end latency spans submit -> finish and decomposes into
        # the queue/prefill/decode breakdown (the pre-fix timer started
        # after prefill and missed the first two entirely)
        assert r.queue_s >= 0 and r.prefill_s > 0 and r.decode_s > 0
        assert r.latency_s == pytest.approx(
            r.queue_s + r.prefill_s + r.decode_s, rel=1e-6)
        assert r.latency_s > r.decode_s  # prefill is visible in the total
    # the 5th request waited for a slot: real queue time on record
    assert done[-1].finish_s > done[0].finish_s


def test_oversized_and_empty_prompts_rejected(setup, srv):
    cfg, _, _ = setup
    base_rejected = len(srv.rejected)
    too_long = srv.submit(Request(uid=100, prompt=_prompt(cfg, 64),
                                  max_new_tokens=4))
    empty = srv.submit(Request(
        uid=101, prompt=np.zeros(0, np.int32), max_new_tokens=4))
    for r, frag in ((too_long, "max_len"), (empty, "empty")):
        assert r.status == REJECTED and not r.ok
        assert frag in r.error
        assert r.output == [] and r.latency_s is None
    assert len(srv.rejected) == base_rejected + 2
    assert not srv.queue  # neither was admitted
    # the slot cache is uncorrupted: a valid request still serves
    ok = srv.submit(Request(uid=102, prompt=_prompt(cfg, 6),
                            max_new_tokens=3))
    assert srv.run_until_drained() == [ok] and ok.status == DONE


def test_eos_and_too_long_termination(setup, srv):
    cfg, _, _ = setup
    prompt = _prompt(cfg, 8, seed=7)
    ref = srv.submit(Request(uid=110, prompt=prompt, max_new_tokens=6))
    srv.run_until_drained()
    # greedy decode is deterministic: replaying the same prompt with
    # eos_id set to a known upcoming token must stop right there
    eos = ref.output[2]
    if eos not in ref.output[:2]:  # eos earlier would end sooner
        again = srv.submit(Request(uid=111, prompt=prompt,
                                   max_new_tokens=6, eos_id=int(eos)))
        srv.run_until_drained()
        assert again.output == ref.output[:3]
        assert again.status == DONE
    # near-full context: generation is cut off at max_len, not run over
    long = srv.submit(Request(uid=112, prompt=_prompt(cfg, 55),
                              max_new_tokens=100))
    srv.run_until_drained()
    assert long.status == DONE
    assert len(long.output) < 100
    assert 55 + len(long.output) >= srv.max_len - 2


def test_interleaved_vs_sequential_parity(setup, srv):
    cfg, _, _ = setup
    pa, pb = _prompt(cfg, 9, seed=11), _prompt(cfg, 7, seed=12)
    # sequential references, one at a time on the drained server
    ra = srv.submit(Request(uid=120, prompt=pa, max_new_tokens=10))
    srv.run_until_drained()
    rb = srv.submit(Request(uid=121, prompt=pb, max_new_tokens=6))
    srv.run_until_drained()
    # interleaved: B joins while A is mid-decode
    ia = srv.submit(Request(uid=122, prompt=pa, max_new_tokens=10))
    for _ in range(3):
        srv.step()
    ib = srv.submit(Request(uid=123, prompt=pb, max_new_tokens=6))
    srv.run_until_drained()
    assert ia.output == ra.output
    assert ib.output == rb.output


def test_abandoned_requests_marked_loudly(setup, srv):
    cfg, _, _ = setup
    base_abandoned = len(srv.abandoned)
    active = [srv.submit(Request(uid=130 + i, prompt=_prompt(cfg, 5, seed=i),
                                 max_new_tokens=500))
              for i in range(2)]
    queued = srv.submit(Request(uid=140, prompt=_prompt(cfg, 5),
                                max_new_tokens=4))
    done = srv.run_until_drained(max_steps=3)
    # nothing finished — but nothing is silent either
    assert done == []
    assert len(srv.abandoned) == base_abandoned + 3
    for r in active:
        assert r.status == ABANDONED and not r.ok
        assert r.latency_s is None and r.decode_s is None
        assert r.output  # partial generation is preserved
    assert queued.status == ABANDONED and queued.output is None
    # the server recovered its capacity: slots free, queue empty
    assert sorted(srv.free) == [0, 1] and not srv.active and not srv.queue
    ok = srv.submit(Request(uid=141, prompt=_prompt(cfg, 5),
                            max_new_tokens=3))
    assert srv.run_until_drained() == [ok] and ok.status == DONE


# ---------------------------------------------------------------- insert
INSERT_ARCHS = ["qwen2-1.5b", "moonlight-16b-a3b"]   # dense; MLA + ``lead``


@pytest.fixture(scope="module", params=INSERT_ARCHS)
def arch_setup(request):
    cfg = get_config(request.param, reduced=True).with_(
        dtype=jnp.float32, param_dtype=jnp.float32, remat=False)
    return cfg, T.init_params(jax.random.PRNGKey(0), cfg)


def _filled_cache(cfg, n_slots, max_len, seed=0):
    """A batched cache with every leaf random, so that a write outside
    the slot would show."""
    rng = np.random.default_rng(seed)

    def fill(a):
        if jnp.issubdtype(a.dtype, jnp.integer):
            return jnp.asarray(rng.integers(0, max_len, a.shape), a.dtype)
        return jnp.asarray(rng.standard_normal(a.shape), a.dtype)

    return jax.tree.map(fill, T.init_cache(cfg, n_slots, max_len))


def _req_cache(srv, cfg, n=7):
    _, rc, *_ = srv._prefill(srv.params,
                             {"tokens": jnp.asarray(_prompt(cfg, n)[None])})
    return rc


@pytest.mark.parametrize("slot", ["first", "last"])
def test_jitted_insert_matches_eager(arch_setup, slot):
    cfg, params = arch_setup
    srv = Server(params, cfg, n_slots=3, max_len=32)
    slot = 0 if slot == "first" else srv.n_slots - 1
    cache, rc = _filled_cache(cfg, 3, 32), _req_cache(srv, cfg)
    want = jax.tree.map(np.asarray, _insert_slot(cache, rc, slot))
    got = srv._insert(cache, rc, np.int32(slot))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert "pos" in got and (cfg.n_dense_lead == 0) == ("lead" not in got)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.shape == w.shape and g.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(g), w)


def test_insert_donates_the_batched_cache(arch_setup):
    cfg, params = arch_setup
    srv = Server(params, cfg, n_slots=3, max_len=32)
    old, rc = _filled_cache(cfg, 3, 32), _req_cache(srv, cfg)
    new = srv._insert(old, rc, np.int32(1))
    jax.block_until_ready(new)
    assert all(a.is_deleted() for a in jax.tree.leaves(old))
    assert not any(a.is_deleted() for a in jax.tree.leaves(rc))


def test_insert_compiles_once_for_every_slot(arch_setup):
    cfg, params = arch_setup
    srv = Server(params, cfg, n_slots=3, max_len=32)
    for i in range(3):
        srv.submit(Request(uid=i, prompt=_prompt(cfg, 5 + 2 * i, seed=i),
                           max_new_tokens=3))
    srv.step()
    assert sorted(srv.active) == [0, 1, 2]
    assert srv._insert._cache_size() == 1
    assert len(srv.run_until_drained()) == 3


def test_moe_server_matches_one_slot_server():
    """Latent-attention MoE served in three slots gives each request the
    tokens a one-slot server gives it alone."""
    cfg = get_config("moonlight-16b-a3b", reduced=True).with_(
        dtype=jnp.float32, param_dtype=jnp.float32, remat=False)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    reqs = [Request(uid=i, prompt=_prompt(cfg, 4 + 3 * i, seed=20 + i),
                    max_new_tokens=3 + i) for i in range(4)]
    srv = Server(params, cfg, n_slots=3, max_len=32)
    for r in reqs:
        srv.submit(r)
    batched = {r.uid: r.output for r in srv.run_until_drained()}
    assert len(batched) == 4
    solo = Server(params, cfg, n_slots=1, max_len=32)
    for r in reqs:
        alone = solo.submit(Request(uid=r.uid, prompt=r.prompt,
                                    max_new_tokens=r.max_new_tokens))
        solo.run_until_drained()
        assert alone.output == batched[r.uid], r.uid
