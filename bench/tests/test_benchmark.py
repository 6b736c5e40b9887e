"""``BENCHMARK.json`` against the rules its format keeps, and the command's
refusal to run without a TPU."""
import importlib
import json
import os
import re
import subprocess
import sys

import pytest

from bench.run import ROOT

SPEC = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert 1 <= SPEC["run_seconds"] <= 51


def test_names_and_entries():
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in SPEC[k]]
    assert all(NAME.match(n) for n in names)
    assert len(set(x["name"] for x in SPEC["end_to_end"] + SPEC["per_layer"])
               ) == len(SPEC["end_to_end"]) + len(SPEC["per_layer"])
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/configs/")
        conf = json.load(open(os.path.join(ROOT, c["file"])))
        assert set(c["reduced"]) == set(conf["reduced"])
        assert set(c["reduced"]) == set(conf["source_values"])
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        assert w["chips"] == 1 and len(w["why"]) <= 200
        assert os.path.exists(os.path.join(ROOT, "bench", "traffic",
                                           w["traffic"] + ".json"))
    assert {w["config"] for w in SPEC["workloads"]} == {
        c["name"] for c in SPEC["configs"]}
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e
    for m in SPEC["end_to_end"]:
        assert UNIT.match(m["unit"]) and m["source"] == "host_clock"
        assert m["better"] in ("lower", "higher")
        assert 0.01 <= m["bound"] <= 0.25


def test_every_per_layer_metric_has_a_reader():
    cells = {w["name"] for w in SPEC["workloads"]}
    for m in SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["moves"] in {
            e["name"] for e in SPEC["end_to_end"]}
        assert set(m["workloads"]) <= cells
        spec = json.load(open(os.path.join(ROOT, "bench", "metrics",
                                           m["name"] + ".json")))
        reader = importlib.import_module(f"bench.readers.{spec['reader']}")
        assert callable(reader.read)
        if m["name"].endswith(("share", "mfu")):
            assert m["unit"] == "%"


def test_every_cell_reports_an_end_to_end_and_a_per_layer_metric():
    for w in SPEC["workloads"]:
        mine = [m for m in SPEC["end_to_end"]
                if w["name"] in m.get("workloads", [w["name"]])]
        assert len(mine) >= 2
        assert any(w["name"] in m["workloads"] for m in SPEC["per_layer"])


@pytest.mark.parametrize("trace", ["0", "1"])
def test_no_tpu_no_result(trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", "qwen2-1.5b.chat", "--seed", "1", "--seconds", "1",
         "--trace", trace], capture_output=True, text=True, env=env,
        timeout=120)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "not a TPU" in p.stderr


@pytest.mark.parametrize("name", [w["name"] for w in SPEC["workloads"]])
def test_a_run_reports_its_cell_metrics(name):
    """A tiny run on the CPU gives every end-to-end metric of its cell, and
    every per-layer metric but those read from a device trace."""
    from bench import run as bench_run
    from bench.tests.harness import run_tiny, tiny_cell
    c = tiny_cell(name)
    run = run_tiny(name)
    device = {"kind": "TPU v5 lite"}
    e2e = bench_run.read_metrics(c, run, 1.0, False, device)
    assert set(e2e) == {m["name"] for m in c["end_to_end"]}
    per_layer = bench_run.read_metrics(c, run, 1.0, True, device)
    want = {m["name"] for m in c["per_layer"]
            if m["source"] != "device_trace"}
    assert set(per_layer) == want
    assert all(v["value"] > 0 for v in {**e2e, **per_layer}.values())
