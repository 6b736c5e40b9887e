"""The trace reduction: on planes built by hand, and on a small trace
recorded on a v5e (``record_trace.py``)."""
import os
from types import SimpleNamespace as NS

import pytest

from bench import trace_reduce, xplane
from bench.readers import program_device_ms

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


# ---- per program and per scope
SCOPES = ("embed", "attention", "kv_update", "mlp", "lm_head")
DECODE, PREFILL = "jit_serve_decode(11)", "jit_serve_prefill(22)"


def _op(name, start, dur, op_name=None):
    return NS(name=f"%{name} = f32[2] op()", start_ns=start,
              duration_ns=dur, op_name=op_name)


def _hlo():
    """The decode module's compiled HLO: the layer loop's body holds an
    attention fusion, a fusion without metadata whose fused ops are mostly
    ``kv_update``, an ``mlp`` fusion, and a conditional in ``attention``
    whose branch holds a slice without metadata."""
    m = xplane.MESSAGES["HloModuleProto"](name="jit_serve_decode")
    comps = {
        1: ("main", [("while.1", "while", "jit(serve_decode)/while", [2, 3]),
                     ("copy.5", "copy", "", []),
                     ("fusion.6", "fusion",
                      "jit(serve_decode)/lm_head/dot_general", [])]),
        2: ("body", [("fusion.2", "fusion",
                      "jit(serve_decode)/while/body/attention/dot_general",
                      []),
                     ("fusion.3", "fusion", "", [4]),
                     ("fusion.4", "fusion",
                      "jit(serve_decode)/while/body/mlp/dot_general", []),
                     ("conditional.10", "conditional",
                      "jit(serve_decode)/while/body/attention/cond", [5])]),
        3: ("cond", [("lt.1", "compare", "jit(serve_decode)/while/lt", [])]),
        4: ("fused_kv", [
            ("param_0", "parameter", "", []),
            ("scatter.1", "scatter",
             "jit(serve_decode)/while/body/attention/kv_update/scatter", []),
            ("add.1", "add",
             "jit(serve_decode)/while/body/attention/kv_update/add", []),
            ("select.1", "select",
             "jit(serve_decode)/while/body/attention/select_n", [])]),
        5: ("branch", [("slice.9", "slice", "", [])]),
    }
    for cid, (cname, instrs) in comps.items():
        c = m.computations.add(name=cname, id=cid)
        for name, opcode, op_name, called in instrs:
            i = c.instructions.add(name=name, opcode=opcode)
            i.metadata.op_name = op_name
            i.called_computation_ids.extend(called)
    return m


def _program_planes():
    host = NS(name="/host:CPU", lines=[_line("python", [
        ("bench.window", 0, 10_000)])])
    at = "jit(serve_decode)/while/body/attention/dot_general"
    mlp = "jit(serve_decode)/while/body/mlp/dot_general"
    head = "jit(serve_decode)/lm_head/dot_general"
    ops = [
        # decode, first execution (100 .. 1100): the loop (100 .. 700)
        # holds attention 200, the fusion without metadata 100, mlp 250
        # and the branch's slice 50; then a copy without metadata 100 and
        # the head 200; 100 ns idle at the end of the module
        _op("while.1", 100, 600, "jit(serve_decode)/while"),
        _op("fusion.2", 100, 200, at),
        _op("fusion.3", 300, 100),
        _op("fusion.4", 400, 250, mlp),
        _op("slice.9", 650, 50),
        _op("copy.5", 700, 100),
        _op("fusion.6", 800, 200, head),
        # decode, second execution (2000 .. 2500): head 300, copy 100
        _op("fusion.6", 2000, 300, head),
        _op("copy.5", 2300, 100),
        # prefill (3000 .. 3400): embed 400
        _op("fusion.1", 3000, 400, "jit(serve_prefill)/embed/gather"),
        # decode, cut by the window's close: not counted
        _op("fusion.6", 9900, 200, head),
    ]
    dev = NS(name="/device:TPU:0", lines=[
        _line("XLA Modules", [(DECODE, 100, 1000), (DECODE, 2000, 500),
                              (PREFILL, 3000, 400), (DECODE, 9900, 200)]),
        NS(name="XLA Ops", events=ops)])
    return [host, dev]


def test_programs_and_scopes():
    r = trace_reduce.reduce_planes(_program_planes(), SCOPES,
                                   {DECODE: _hlo()})
    progs = r["programs"]
    assert set(progs) == {"jit_serve_decode", "jit_serve_prefill"}
    dec, pre = progs["jit_serve_decode"], progs["jit_serve_prefill"]
    assert dec["executions"] == 2 and pre["executions"] == 1
    # (900 + 400) ns over two executions
    assert dec["device_ms"] == pytest.approx(650e-6)
    assert dec["scope_ms"] == pytest.approx({
        "attention": 125e-6,      # 200 + the branch's 50, per execution
        "kv_update": 50e-6,       # the fusion, from its fused ops
        "mlp": 125e-6,
        "lm_head": 250e-6,        # 200 + 300
        "unscoped": 100e-6})      # the copies; the loop's self time is 0
    assert pre == {"executions": 1, "device_ms": pytest.approx(400e-6),
                   "scope_ms": {"embed": pytest.approx(400e-6)}}
    for p in progs.values():
        assert sum(p["scope_ms"].values()) == pytest.approx(p["device_ms"])


def test_ops_without_an_op_name_and_no_hlo_are_unscoped():
    r = trace_reduce.reduce_planes(_program_planes(), SCOPES)
    scopes = r["programs"]["jit_serve_decode"]["scope_ms"]
    # the two copies, the fusion and the slice: 350 ns over two executions
    assert scopes["unscoped"] == pytest.approx(175e-6)
    assert "kv_update" not in scopes


@pytest.mark.parametrize("op_name,scope", [
    ("jit(serve_decode)/while/body/attention/kv_update/scatter",
     "kv_update"),
    ("jit(train_step)/transpose(jvp(mlp))/dot_general", "mlp"),
    ("jit(serve_decode)/while/body/dynamic_slice", "unscoped"),
    ("jit(attention_like)/dot_general", "unscoped")])
def test_innermost_scope_of_an_op_name(op_name, scope):
    assert trace_reduce.op_scope(op_name, SCOPES) == scope


def test_reader_takes_a_program_and_a_scope():
    r = trace_reduce.reduce_planes(_program_planes(), SCOPES,
                                   {DECODE: _hlo()})
    run = NS(trace=r)
    assert program_device_ms.read(run, {}, {}, "jit_serve_decode") == (
        pytest.approx(650e-6))
    assert program_device_ms.read(run, {}, {}, "jit_serve_decode",
                                  "kv_update") == pytest.approx(50e-6)
    assert program_device_ms.read(run, {}, {}, "jit_serve_prefill",
                                  "mlp") is None
    assert program_device_ms.read(run, {}, {}, "jit_train_step") is None
    assert program_device_ms.read(NS(trace=None), {}, {},
                                  "jit_serve_decode") is None


def test_existing_readings_do_not_depend_on_the_scopes():
    with_scopes = trace_reduce.reduce_planes(_program_planes(), SCOPES,
                                             {DECODE: _hlo()})
    without = trace_reduce.reduce_planes(_program_planes())
    for k in ("window_s", "busy_s", "device_ops", "idle_gaps",
              "idle_by_host_span"):
        assert with_scopes[k] == without[k]
    assert set(without["programs"]["jit_serve_decode"]["scope_ms"]) == {
        "unscoped"}


@pytest.mark.skipif(not os.path.exists(DATA), reason="no recorded trace")
def test_recorded_trace_reads_as_the_profiler_reads_it():
    """The planes read from the file are the events ``jax.profiler``'s own
    reader gives, with each op's ``op_name``; the module's compiled HLO
    comes with them."""
    from jax.profiler import ProfileData
    planes, hlo = xplane.read(DATA)
    theirs = ProfileData.from_file(DATA).planes
    assert [p.name for p in planes] == [p.name for p in theirs]
    for ours, their in zip(planes, theirs):
        for a, b in zip(ours.lines, their.lines):
            assert a.name == b.name
            assert [(e.name, e.start_ns, e.duration_ns) for e in a.events] \
                == [(e.name, e.start_ns, e.duration_ns) for e in b.events]
    ops = [e for p in planes for ln in p.lines if ln.name == "XLA Ops"
           for e in ln.events]
    assert {e.op_name for e in ops if "convolution" in e.name} == {
        "jit(<lambda>)/dot_general:"}
    assert list(hlo) == ["jit__lambda(14836250070554842513)"]
    names = {i.name for c in hlo["jit__lambda(14836250070554842513)"]
             .computations for i in c.instructions}
    assert {"convolution_tanh_fusion", "copy-start"} <= names
    prog = trace_reduce.reduce_file(DATA, SCOPES)["programs"]
    # twelve ran; the device's clock reads the first before the window opens
    assert prog["jit__lambda"]["executions"] == 11
    assert set(prog["jit__lambda"]["scope_ms"]) == {"unscoped"}
    assert 0.05 < prog["jit__lambda"]["device_ms"] < 0.2
