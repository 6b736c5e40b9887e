"""Workload zoo + cross-network surrogate transfer + bench artifacts.

Covers ``repro.compiler.zoo`` (registry, typed networks, the pod proxy
oracle), ``repro.compiler.surrogate_store`` (JSONL round-trip, dedup,
schema-mismatch rejection, dimension/network filtering, warm starts),
the ``surrogates=`` wiring through ``Session`` and ``netopt`` (transfer
stats, GBT-ranked warm seeding, the warm-from-self == record-replay
invariant), the new surrogate fields in the report round-trips, and the
hardened ``repro-bench/2`` artifact writer.
"""
import glob
import importlib.util
import json
import os
import sys

import numpy as np
import pytest

from repro.compiler.netopt import (NetOptConfig, NetworkCoOptimizer,
                                   NetworkReport, network_hw_frozen_tune)
from repro.compiler.session import Session, SessionReport
from repro.compiler.surrogate_store import (RecordingGBT, SCHEMA,
                                            SurrogateSchemaError,
                                            SurrogateStore)
from repro.compiler.task import TuningTask
from repro.compiler.zoo import NetworkTask, ZOO, get_network, network_names
from repro.core import mappo
from repro.core.cost_model import GBTModel
from repro.core.design_space import DesignSpace
from repro.core.tuner import TunerConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY = TunerConfig(iteration_opt=2, b_measure=6, episodes_per_iter=2,
                   mappo=mappo.MappoConfig(n_steps=12, n_envs=8),
                   gbt_rounds=8)
WL_A1 = dict(b=1, h=14, w=14, ci=256, co=256, kh=3, kw=3, stride=1, pad=1)
WL_A2 = dict(b=1, h=28, w=28, ci=128, co=128, kh=3, kw=3, stride=1, pad=1)
WL_B1 = dict(b=1, h=14, w=14, ci=128, co=256, kh=3, kw=3, stride=1, pad=1)
WL_B2 = dict(b=1, h=28, w=28, ci=128, co=256, kh=3, kw=3, stride=1, pad=1)


def _net(name, *wls):
    return [TuningTask.from_space(f"{name}{i}", DesignSpace.for_conv2d(wl))
            for i, wl in enumerate(wls)]


def _tiny_netcfg(**kw):
    base = dict(seed_candidates=2, hw_rounds=1, hw_per_round=1,
                layer_budget=6, refine_budget=4, tuner=TINY)
    base.update(kw)
    return NetOptConfig(**base)


def _load_benchmarks(name):
    path = os.path.join(ROOT, "benchmarks", f"{name}.py")
    if os.path.join(ROOT, "benchmarks") not in sys.path:
        sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------------------------------- zoo

def test_zoo_registry_covers_required_families():
    names = network_names()
    assert len(names) >= 5
    assert {"resnet-18", "vgg-11", "mobilenet-dw", "bert-gemm",
            "pod-cells"} <= set(names)
    kinds = {get_network(n).kind for n in names}
    assert {"conv", "gemm", "pod"} <= kinds
    with pytest.raises(KeyError):
        get_network("no-such-network")


@pytest.mark.parametrize("name", sorted(ZOO))
def test_zoo_networks_build_and_measure(name):
    net = get_network(name)
    assert isinstance(net, NetworkTask)
    assert net.n_tasks >= 3 and net.n_layers >= net.n_tasks
    assert name in net.summary()
    task_names = [t.name for t in net.tasks]
    assert len(set(task_names)) == len(task_names)
    for t in net.tasks[:2]:
        d = t.descriptor()
        assert d.shape == (11,) and np.isfinite(d).all()
        # one oracle measurement per network family stays cheap and finite
        oracle = t.make_oracle()
        lat, feats = oracle.measure(np.zeros((1, t.space.n_knobs), np.int64))
        assert np.isfinite(lat).all() and lat[0] > 0
        assert feats.shape == (1, 18)


def test_zoo_pod_proxy_prefers_parallelism():
    """The pod proxy must reward sharding enough that search has signal:
    TP=4 on the train cell beats TP=max on nothing else changed? No —
    just assert the proxy separates configs instead of being flat."""
    net = get_network("pod-cells")
    space = net.tasks[0].space
    cfgs = np.zeros((space.n_knobs,), np.int64)
    lats = []
    for j in range(len(space.choices[0])):
        c = cfgs.copy()
        c[0] = j
        lats.append(float(space.measure(c[None])[0]))
    assert len(set(lats)) > 1  # model-axis degree matters
    assert all(np.isfinite(lats))


# -------------------------------------------------------- surrogate store

def test_store_roundtrip_dedup_and_filters(tmp_path):
    path = str(tmp_path / "s.jsonl")
    store = SurrogateStore(path)
    assert not store.exists()
    assert store.rows("sw", 18)[0].shape == (0, 18)
    x = np.arange(18, dtype=np.float32) / 10
    assert store.add("sw", x, 1.5, network="netA")
    assert not store.add("sw", x, 1.5, network="netA")   # exact dup
    assert store.add("sw", x, 2.5, network="netB")       # new target
    assert store.add("hw", np.ones(14), 0.5, network="netA")
    # a fresh instance reloads (and re-dedups) from disk
    back = SurrogateStore(path)
    assert back.counts() == {"sw": 2, "hw": 1}
    assert back.networks() == ("netA", "netB")
    X, y = back.rows("sw", 18)
    assert X.shape == (2, 18) and set(y.tolist()) == {1.5, 2.5}
    X, y = back.rows("sw", 18, exclude_network="netA")
    assert y.tolist() == [2.5]
    assert back.rows("sw", 14)[0].shape == (0, 14)  # dim filter
    # family filter: pod rows reuse the 18-dim layout with different
    # semantics and must never reach a core GBT (and vice versa)
    assert back.add("sw", x + 1, 3.5, network="podnet", family="pod")
    assert back.rows("sw", 18)[1].tolist() == [1.5, 2.5]
    assert back.rows("sw", 18, family="pod")[1].tolist() == [3.5]
    assert not back.add("sw", x, 2.5, network="netB")  # dup across reload
    # merge is schema-checked, deduplicated, and family-preserving
    other = SurrogateStore(str(tmp_path / "t.jsonl"))
    assert other.merge_from(path) == 4
    assert other.merge_from(path) == 0
    assert other.rows("sw", 18, family="pod")[1].tolist() == [3.5]
    # readonly stores never write
    ro = SurrogateStore(path, readonly=True)
    assert not ro.add("sw", np.zeros(18), 9.0)
    assert SurrogateStore(path).counts() == {"sw": 3, "hw": 1}
    with pytest.raises(ValueError):
        store.add("bogus-kind", x, 0.0)


def test_store_rejects_schema_mismatch(tmp_path):
    path = str(tmp_path / "stale.jsonl")
    with open(path, "w") as f:
        f.write(json.dumps({"schema": "repro-surrogate/0", "kind": "sw",
                            "dim": 2, "x": [0.0, 1.0], "y": 1.0}) + "\n")
    with pytest.raises(SurrogateSchemaError):
        SurrogateStore(path).counts()
    with open(path, "w") as f:
        f.write(json.dumps({"schema": SCHEMA, "kind": "wat", "dim": 1,
                            "x": [0.0], "y": 1.0}) + "\n")
    with pytest.raises(SurrogateSchemaError):
        SurrogateStore(path).rows("sw", 18)
    # and a valid store keeps working after the check
    ok = str(tmp_path / "ok.jsonl")
    s = SurrogateStore(ok)
    s.add("sw", np.zeros(18), 1.0)
    assert SurrogateStore(ok).counts()["sw"] == 1


def test_recording_gbt_tees_updates_but_not_primes(tmp_path):
    store = SurrogateStore(str(tmp_path / "s.jsonl"))
    gbt = RecordingGBT(n_rounds=4, n_features=18, store=store,
                       network="netA")
    rng = np.random.default_rng(0)
    Xp, yp = rng.random((5, 18)), rng.random(5)
    gbt.prime(Xp, yp)                      # warm start: not recorded
    assert store.counts()["sw"] == 0
    X, y = rng.random((3, 18)), rng.random(3)
    gbt.update(X, y)                       # real training rows: recorded
    assert store.counts()["sw"] == 3
    assert gbt.n_samples == 8
    # warm_start routes through prime (no re-recording) and respects
    # the exclude-own-network rule
    g2 = GBTModel(n_rounds=4, n_features=18)
    assert store.warm_start(g2, "sw") == 3
    assert g2.n_samples == 3
    g3 = RecordingGBT(n_rounds=4, n_features=18, store=store,
                      network="netB")
    assert store.warm_start(g3, "sw", exclude_network="netA") == 0
    assert store.counts()["sw"] == 3
    # executor failure-penalty rows train the in-run GBT but are never
    # persisted (a transient worker crash must not poison every later
    # network's warm start); deterministic analytical infeasibility
    # (the 1e12 sentinel) IS transferable knowledge and passes through
    from repro.compiler.oracle import Oracle
    lats = np.asarray([Oracle.penalty_latency, 1e12, 1e-4])
    gbt.update(rng.random((3, 18)), -np.log(lats))
    assert gbt.n_samples == 11
    assert store.counts()["sw"] == 5  # penalty row dropped, other 2 kept


# --------------------------------------------------------------- session

def test_session_saves_and_warm_starts_sw_rows(tmp_path):
    path = str(tmp_path / "surr.jsonl")
    t_a = TuningTask.from_space("a", DesignSpace.for_conv2d(WL_A1))
    t_b = TuningTask.from_space("b", DesignSpace.for_conv2d(WL_B1))
    sr_a = Session(t_a, tuner=TINY, budget=6, surrogates=path).run()
    assert sr_a.surrogates["warm_sw_rows"] == 0
    n_rows = SurrogateStore(path).counts()["sw"]
    assert n_rows >= 6
    sr_b = Session(t_b, tuner=TINY, budget=6, surrogates=path).run()
    assert sr_b.surrogates["warm_sw_rows"] == n_rows
    # re-running the same task set excludes its own rows (self-transfer
    # is a no-op by design)
    sr_a2 = Session(t_a, tuner=TINY, budget=6, surrogates=path).run()
    assert sr_a2.surrogates["warm_sw_rows"] == \
        SurrogateStore(path).counts()["sw"] - n_rows
    with pytest.raises(ValueError):
        Session(t_a, tuner=TINY, budget=4, surrogates=path,
                gbt=GBTModel(n_rounds=4))
    with pytest.raises(ValueError):
        Session(t_a, tuner=TINY, budget=4, surrogates=path,
                share_cost_model=False)


# ----------------------------------------------------- netopt transfer

def test_netopt_transfer_stats_and_warm_seeding(tmp_path):
    cfg = _tiny_netcfg(seed_candidates=3)
    path = str(tmp_path / "surr.jsonl")
    net_a, net_b = _net("a", WL_A1, WL_A2), _net("b", WL_B1, WL_B2)
    ra = NetworkCoOptimizer(net_a, cfg, name="netA",
                            surrogates=path).run()
    assert ra.surrogates["warm_hw_rows"] == 0
    assert ra.surrogates["warm_sw_rows"] == 0
    assert not ra.surrogates["warm_seeded"]
    # >= : the refine pass re-evaluates the winner and appends one more
    # hw row whenever it improves the candidate's latency
    assert ra.surrogates["hw_rows_saved"] >= ra.hw_candidates
    counts = SurrogateStore(path).counts()
    assert counts["hw"] == ra.surrogates["hw_rows_saved"]
    assert counts["sw"] > 0

    rb = NetworkCoOptimizer(net_b, cfg, name="netB",
                            surrogates=path).run()
    assert rb.surrogates["warm_hw_rows"] == counts["hw"]
    assert rb.surrogates["warm_sw_rows"] == counts["sw"]
    assert rb.surrogates["warm_seeded"]
    # warm seeding keeps the two guaranteed seeds: the default chip and
    # the largest geometry (frontier probe)
    default = rb.trace[0]["hw"]
    hw = NetworkCoOptimizer(net_b, cfg, name="x").hw
    assert default == dict(zip(
        ("tile_b", "tile_ci", "tile_co"), hw.default_values(net_b)))
    assert rb.trace[1]["hw"] == dict(zip(
        ("tile_b", "tile_ci", "tile_co"),
        (c[-1] for c in hw.choices)))
    # the frozen baseline records transfer stats too (it shares the store
    # machinery), and co-opt still dominates it at equal budget
    frozen = network_hw_frozen_tune(net_b, cfg, name="netB-frozen",
                                    surrogates=path)
    assert frozen.surrogates["warm_sw_rows"] > 0
    assert rb.network_latency <= frozen.network_latency


def test_netopt_warm_from_self_still_replays_with_zero_measurements(
        tmp_path):
    """Transfer and replay must stay orthogonal: re-running a network
    against its own records AND its own store (which may also hold other
    networks' rows) replays bit-identically — own-network rows are
    excluded from the warm start, so the search trajectory is unchanged
    and every measurement hits the record cache."""
    cfg = _tiny_netcfg(seed_candidates=3)
    store = str(tmp_path / "surr.jsonl")
    records = str(tmp_path / "b.records.jsonl")
    # the store starts with a foreign network's rows (the realistic case)
    NetworkCoOptimizer(_net("a", WL_A1), cfg, name="netA",
                       surrogates=store).run()
    net_b = _net("b", WL_B1, WL_B2)
    r1 = NetworkCoOptimizer(net_b, cfg, records=records, name="netB",
                            surrogates=store).run()
    assert r1.total_measurements > 0
    r2 = NetworkCoOptimizer(net_b, cfg, records=records, name="netB",
                            surrogates=store).run()
    assert r2.total_measurements == 0
    assert r2.hw_config == r1.hw_config
    assert r2.network_latency == r1.network_latency
    assert r2.surrogates["warm_hw_rows"] == r1.surrogates["warm_hw_rows"]


# ------------------------------------------------- report round-trips

def test_network_report_roundtrips_surrogate_fields(tmp_path):
    cfg = _tiny_netcfg()
    rep = NetworkCoOptimizer(_net("a", WL_A1), cfg, name="netA",
                             surrogates=str(tmp_path / "s.jsonl")).run()
    assert rep.surrogates["hw_rows_saved"] >= 1
    back = NetworkReport.from_dict(json.loads(json.dumps(rep.to_dict())))
    assert back.surrogates == rep.surrogates
    assert back.measurements_to(rep.network_latency) == \
        rep.measurements_to(rep.network_latency)
    assert rep.measurements_to(0.0) is None
    # an infinitely lax target is hit inside the FIRST candidate's session
    # (the within-candidate trajectory resolves it at or before the
    # candidate's cumulative spend)
    hit = rep.measurements_to(float("inf"))
    assert 0 < hit <= int(rep.trace[0]["cum_measurements"])
    # old documents (no surrogates key) deserialize with the default
    d = rep.to_dict()
    d.pop("surrogates")
    assert NetworkReport.from_dict(d).surrogates == {}


def test_session_report_roundtrips_surrogate_fields(tmp_path):
    t = TuningTask.from_space("a", DesignSpace.for_conv2d(WL_A1))
    sr = Session(t, tuner=TINY, budget=6,
                 surrogates=str(tmp_path / "s.jsonl")).run()
    back = SessionReport.from_dict(json.loads(json.dumps(sr.to_dict())))
    assert back.surrogates == sr.surrogates
    assert back.single.to_dict() == sr.single.to_dict()  # TuneReport trip
    d = sr.to_dict()
    d.pop("surrogates")
    assert SessionReport.from_dict(d).surrogates == {}


# ------------------------------------------------------ bench artifacts

def test_write_bench_artifact_includes_git_rev_and_validates(tmp_path):
    tr = _load_benchmarks("tuning_runs")
    path = str(tmp_path / "BENCH_x.json")
    doc = tr.write_bench_artifact(path, "x", {"m": 1.0}, config={"n": 2})
    assert doc["schema"] == tr.BENCH_SCHEMA == "repro-bench/2"
    assert doc["git_rev"] and isinstance(doc["git_rev"], str)
    assert tr.validate_bench_doc(json.load(open(path))) == doc
    for bad in (
            {**doc, "schema": "repro-bench/0"},
            {**doc, "metrics": {}},
            {**doc, "metrics": {"m": float("nan")}},
            {**doc, "metrics": {"m": {"nested": 1.0}}},
            {**doc, "metrics": {"m": True}},
            {**doc, "git_rev": ""},
            {**doc, "config": None},
    ):
        with pytest.raises(ValueError):
            tr.validate_bench_doc(bad)
    with pytest.raises(ValueError):  # rejected before touching disk
        tr.write_bench_artifact(str(tmp_path / "BENCH_bad.json"), "x",
                                {"m": float("inf")}, config={})
    assert not os.path.exists(str(tmp_path / "BENCH_bad.json"))


def test_committed_bench_artifacts_are_valid():
    tr = _load_benchmarks("tuning_runs")
    paths = sorted(glob.glob(os.path.join(ROOT, "BENCH_*.json")))
    assert {os.path.basename(p) for p in paths} >= \
        {"BENCH_netopt.json", "BENCH_transfer.json", "BENCH_hetero.json"}
    for p in paths:
        doc = tr.validate_bench_doc(json.load(open(p)))
        assert doc["git_rev"] != "unknown", p


def test_hetero_bench_artifact_shows_pipeline_win():
    """The committed BENCH_hetero.json must demonstrate the netopt-v2
    headline: on the mixed conv+GEMM network, K=2 pipeline co-optimization
    strictly beats BOTH the single-chip K=1 run and the DiGamma-style
    genetic baseline on end-to-end latency at equal budget."""
    with open(os.path.join(ROOT, "BENCH_hetero.json")) as f:
        doc = json.load(f)
    m = doc["metrics"]
    assert m["k2_network_latency_s"] < m["k1_network_latency_s"]
    assert m["k2_network_latency_s"] < m["genetic_network_latency_s"]
    assert m["k2_speedup_vs_k1"] > 1.0
    assert m["k2_speedup_vs_genetic"] > 1.0
    # the pipeline cut is interior (a real 2-stage partition, not a
    # degenerate everything-on-one-chip split)
    assert 0 < m["k2_cut"] < 12


def test_transfer_bench_artifact_shows_transfer_win():
    """The committed BENCH_transfer.json must demonstrate the headline:
    on at least one zoo pair the transferred run reached the cold run's
    best latency with fewer new measurements, and the warm-from-self leg
    replayed with zero new measurements."""
    with open(os.path.join(ROOT, "BENCH_transfer.json")) as f:
        doc = json.load(f)
    m = doc["metrics"]
    pairs = {k.split("/")[0] for k in m if "/" in k}
    assert pairs
    wins = 0
    for p in pairs:
        assert m[f"{p}/warm_self_new_measurements"] == 0.0
        reached = m[f"{p}/transfer_measurements_to_cold_best"]
        if 0 <= reached < m[f"{p}/cold_measurements_to_best"]:
            wins += 1
    assert wins >= 1, f"no pair shows a transfer win: {m}"
