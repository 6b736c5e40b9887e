"""From a JAX profiler trace (``.xplane.pb``) to the device's busy time, its
idle gaps and its heaviest operations, over the window the harness marks
with a ``bench.window`` host annotation.

- Busy time: the union of the intervals of the device's ``XLA Ops`` events
  inside the window, averaged over the devices that ran any.
- Idle gaps: the rest of the window on the first such device. Each gap is
  named by the ``bench.*`` host annotation (other than ``bench.window``)
  that overlaps it most, or ``host:unmarked``. The harness's annotations
  follow one another on one thread, so the search walks back from the
  last one that starts before the gap ends.
- Device ops: self time per op (an op's time less that of the ops nested
  in it, as a loop holds its body), named ``<program>/<op>`` by the
  ``XLA Modules`` event around it and the op's HLO name, summed over the
  window.
- Programs: for each program (the ``XLA Modules`` event's name with its
  hash stripped: ``jit_serve_decode``), its executions that ran wholly
  inside the window, their ops' self time per execution, and that time
  split by the innermost of the family's named ``scopes`` in each op's
  ``op_name``, or ``unscoped``. An op's ``op_name`` is its own (the
  ``tf_op`` stat of its metadata); an op without one is joined to the
  module's compiled HLO: an instruction that has an ``op_name`` takes its
  scope from it, a fusion without one the scope most of its fused
  instructions carry, any other instruction its caller's (the instruction
  whose called computation holds it), and one with none of these is
  ``unscoped``.

Idle gaps, device ops and programs are read on the first device.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import Counter, defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

TOP = 10
WINDOW = "bench.window"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"

UNSCOPED = "unscoped"

Span = Tuple[float, float, str]       # start_ns, end_ns, name
Op = Tuple[float, float, str, Optional[str]]    # ... and its op_name


def _events(line) -> List[Span]:
    return [(e.start_ns, e.start_ns + e.duration_ns, e.name)
            for e in line.events]


def _ops(line) -> List[Op]:
    return [(e.start_ns, e.start_ns + e.duration_ns, e.name,
             getattr(e, "op_name", None)) for e in line.events]


def program_name(module: str) -> str:
    """``jit_serve_decode(2703800818024598164)`` -> ``jit_serve_decode``."""
    return re.sub(r"\(\d+\)$", "", module)


def op_scope(op_name: str, scopes: Sequence[str]) -> str:
    """The innermost of ``scopes`` that an ``op_name`` names, as a part of
    its path or inside a transform (``transpose(jvp(mlp))``)."""
    found = UNSCOPED
    for part in re.split(r"[^A-Za-z0-9_]+", op_name):
        if part in scopes:
            found = part
    return found


def hlo_scopes(module, scopes: Sequence[str]) -> Dict[str, str]:
    """The scope of every instruction of a compiled module (an
    ``HloModuleProto``), by the join rule of the module docstring."""
    comps = {c.id: c for c in module.computations}
    own, caller, home = {}, {}, {}
    for c in module.computations:
        for i in c.instructions:
            own[i.name] = (op_scope(i.metadata.op_name, scopes)
                           if i.metadata.op_name else None)
            home[i.name] = c.id
            for cid in i.called_computation_ids:
                caller.setdefault(cid, i)
    out: Dict[str, str] = {}

    def scope(i) -> str:
        if i.name not in out:
            s = own[i.name]
            if s is None and i.opcode == "fusion":
                named = [own[f.name] for cid in i.called_computation_ids
                         for f in comps[cid].instructions
                         if own[f.name] not in (None, UNSCOPED)]
                s = Counter(named).most_common(1)[0][0] if named else None
            if s is None:
                up = caller.get(home[i.name])
                s = scope(up) if up is not None else UNSCOPED
            out[i.name] = s
        return out[i.name]

    for c in module.computations:
        for i in c.instructions:
            scope(i)
    return out


def _union(spans: List[Span], lo: float, hi: float) -> List[Tuple[float,
                                                                  float]]:
    out: List[List[float]] = []
    for s, e, _ in sorted(spans):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _gaps(busy, lo, hi):
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def _label(s: float, e: float, marks: List[Span], starts: List[float]
           ) -> str:
    best, name = 0.0, "host:unmarked"
    j = bisect.bisect_left(starts, e) - 1
    while j >= 0 and marks[j][1] > s:
        over = min(e, marks[j][1]) - max(s, marks[j][0])
        if over > best:
            best, name = over, marks[j][2]
        j -= 1
    return name


def reduce_planes(planes, scopes: Sequence[str] = (),
                  hlo: Optional[Dict[str, object]] = None) -> Dict:
    """The reduction of the module docstring; ``hlo`` holds each module's
    compiled HLO by its name, for the ops that carry no ``op_name``."""
    host, devices = [], []
    for p in planes:
        lines = {ln.name: ln for ln in p.lines}
        if p.name.startswith("/device:") and OPS_LINE in lines:
            devices.append((_ops(lines[OPS_LINE]),
                            _events(lines[MODULES_LINE])
                            if MODULES_LINE in lines else []))
        elif p.name.startswith("/host:"):
            for ln in p.lines:
                host += [s for s in _events(ln) if s[2].startswith("bench.")]
    windows = [s for s in host if s[2] == WINDOW]
    if not windows:
        raise ValueError(f"the trace has no {WINDOW!r} annotation")
    lo, hi = windows[0][0], windows[0][1]
    marks = sorted(s for s in host
                   if s[2] != WINDOW and s[1] > lo and s[0] < hi)
    mark_starts = [m[0] for m in marks]
    busies = [_union([op[:3] for op in ops], lo, hi) for ops, _ in devices]
    busies = [b for b in busies if b]
    if not busies:
        raise ValueError("no device operation ran inside the window")
    busy_ns = [sum(e - s for s, e in b) for b in busies]

    ops_ns: Dict[str, float] = defaultdict(float)
    ops, mods = devices[0]
    mods = sorted(mods)
    starts = [m[0] for m in mods]
    selfs = _self_times(ops, lo, hi)
    for s, self_ns, name, _ in selfs:
        j = bisect.bisect_right(starts, s) - 1
        prog = mods[j][2] if j >= 0 and mods[j][1] >= s else "?"
        ops_ns[f"{prog}/{name.split(' = ')[0].lstrip('%')}"] += self_ns
    gaps = sorted(((e - s, _label(s, e, marks, mark_starts))
                   for s, e in _gaps(busies[0], lo, hi)), reverse=True)
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy_ns) / len(busy_ns) / 1e9,
        "device_ops": [[k, v / 1e9] for k, v in sorted(
            ops_ns.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": [[name, ns / 1e9] for ns, name in gaps[:TOP]],
        "idle_by_host_span": {k: v / 1e9 for k, v in _sum_by(gaps).items()},
        "programs": _programs(selfs, mods, lo, hi, scopes, hlo or {}),
    }


def _programs(selfs, mods: List[Span], lo: float, hi: float,
              scopes: Sequence[str], hlo: Dict[str, object]) -> Dict:
    """Per program: ``executions`` that ran wholly inside [lo, hi], the
    self time of their ops (``selfs``, from :func:`_self_times`) per
    execution (``device_ms``), and that time by scope (``scope_ms``, the
    scopes whose ops took any)."""
    runs = sorted(m for m in mods if lo <= m[0] and m[1] <= hi)
    starts = [m[0] for m in runs]
    count: Dict[str, int] = Counter(program_name(m[2]) for m in runs)
    ns: Dict[str, Dict[str, float]] = defaultdict(lambda: defaultdict(float))
    joined: Dict[str, Dict[str, str]] = {}
    for s, self_ns, name, op_name in selfs:
        j = bisect.bisect_right(starts, s) - 1
        if j < 0 or runs[j][1] < s:
            continue
        module = runs[j][2]
        if op_name:
            scope = op_scope(op_name, scopes)
        else:
            if module not in joined:
                joined[module] = (hlo_scopes(hlo[module], scopes)
                                  if module in hlo else {})
            scope = joined[module].get(name.split(" = ")[0].lstrip("%"),
                                       UNSCOPED)
        ns[program_name(module)][scope] += self_ns
    return {prog: {"executions": n,
                   "device_ms": sum(ns[prog].values()) / n / 1e6,
                   "scope_ms": {k: v / n / 1e6
                                for k, v in sorted(ns[prog].items()) if v}}
            for prog, n in sorted(count.items())}


def _self_times(ops: List[Op], lo: float, hi: float):
    """(start, self time, name, op_name) of each op clipped to [lo, hi]."""
    clipped = sorted(((max(s, lo), min(e, hi), n, o) for s, e, n, o in ops
                      if min(e, hi) > max(s, lo)), key=lambda x: (x[0], -x[1]))
    out, stack = [], []       # stack entries: [start, end, name, op, inner]
    for s, e, n, o in clipped:
        while stack and stack[-1][1] <= s:
            t = stack.pop()
            out.append((t[0], t[1] - t[0] - t[4], t[2], t[3]))
        if stack:
            stack[-1][4] += e - s
        stack.append([s, e, n, o, 0.0])
    out += [(t[0], t[1] - t[0] - t[4], t[2], t[3]) for t in stack]
    return out


def _sum_by(gaps) -> Dict[str, float]:
    out: Dict[str, float] = defaultdict(float)
    for ns, name in gaps:
        out[name] += ns
    return dict(out)


def reduce_file(path: str, scopes: Sequence[str] = ()) -> Dict:
    from bench import xplane
    planes, hlo = xplane.read(path)
    return reduce_planes(planes, scopes, hlo)


def reduce_dir(out_dir: str, scopes: Sequence[str] = ()) -> Dict:
    found = glob.glob(os.path.join(out_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise ValueError(f"expected one .xplane.pb under {out_dir}, found "
                         f"{found}")
    return reduce_file(found[0], scopes)
