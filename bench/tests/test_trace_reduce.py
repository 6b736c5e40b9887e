"""The trace reduction: on planes built by hand, and on a small trace
recorded on a v5e (``record_trace.py``)."""
import os
from types import SimpleNamespace as NS

import pytest

from bench import trace_reduce

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "small_trace.xplane.pb")


def _line(name, spans):
    return NS(name=name, events=[NS(name=n, start_ns=s, duration_ns=d)
                                 for n, s, d in spans])


def _planes():
    host = NS(name="/host:CPU", lines=[_line("python", [
        ("bench.window", 100, 900),                     # 100 .. 1000
        ("bench.serve.step", 100, 300),                 # 100 .. 400
        ("bench.serve.wait", 600, 200),                 # 600 .. 800
        ("other", 0, 2000)])])
    dev = NS(name="/device:TPU:0", lines=[
        _line("XLA Modules", [("jit_decode(1)", 50, 400),
                              ("jit_prefill(2)", 800, 150)]),
        _line("XLA Ops", [("%while.1 = (f32[2]) while()", 50, 300),
                          ("%fusion.1 = f32[2] fusion()", 50, 150),
                          ("%fusion.2 = f32[2] fusion()", 200, 150),
                          ("%dot.3 = f32[2] dot()", 820, 100)])])
    # while.1 50..350 holds fusion.1 50..200 and fusion.2 200..350; the
    # window clips both loop and first fusion to start at 100
    return [host, dev]


def test_busy_idle_and_window():
    r = trace_reduce.reduce_planes(_planes())
    assert r["window_s"] == pytest.approx(900e-9)
    # busy: 100..350 and 820..920
    assert r["busy_s"] == pytest.approx(350e-9)


def test_ops_named_by_program():
    r = trace_reduce.reduce_planes(_planes())
    ops = dict(r["device_ops"])
    assert ops == pytest.approx({"jit_decode(1)/while.1": 0.0,
                                 "jit_decode(1)/fusion.1": 100e-9,
                                 "jit_decode(1)/fusion.2": 150e-9,
                                 "jit_prefill(2)/dot.3": 100e-9})


def test_gaps_attributed_to_host_spans():
    r = trace_reduce.reduce_planes(_planes())
    # gaps: 350..820 (the step overlaps 50, the wait 200) and 920..1000
    assert r["idle_gaps"] == [["bench.serve.wait", pytest.approx(470e-9)],
                              ["host:unmarked", pytest.approx(80e-9)]]
    assert r["idle_by_host_span"] == pytest.approx(
        {"bench.serve.wait": 470e-9, "host:unmarked": 80e-9})


def test_no_window_is_an_error():
    planes = _planes()
    planes[0].lines[0].events.pop(0)
    with pytest.raises(ValueError):
        trace_reduce.reduce_planes(planes)


@pytest.mark.skipif(not os.path.exists(DATA), reason="no recorded trace")
def test_recorded_v5e_trace():
    """Twelve 2048^3 bf16 products (about 0.1 ms each on a v5e) inside a
    ~300 ms window of sleeps: the device is busy a small share, every gap
    during a ``bench.serve.wait`` sleep is named by it, and the product
    is the heaviest op."""
    r = trace_reduce.reduce_file(DATA)
    assert 0.25 < r["window_s"] < 0.6
    assert 0 < r["busy_s"] < 0.1 * r["window_s"]
    names = [n for n, _ in r["idle_gaps"]]
    assert "bench.serve.wait" in names
    assert r["idle_by_host_span"]["bench.serve.wait"] > 0.1
    assert len(r["device_ops"]) >= 1
