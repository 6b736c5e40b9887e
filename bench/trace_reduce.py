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
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict
from typing import Dict, List, Tuple

TOP = 10
WINDOW = "bench.window"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"

Span = Tuple[float, float, str]       # start_ns, end_ns, name


def _events(line) -> List[Span]:
    return [(e.start_ns, e.start_ns + e.duration_ns, e.name)
            for e in line.events]


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


def reduce_planes(planes) -> Dict:
    host, devices = [], []
    for p in planes:
        lines = {ln.name: ln for ln in p.lines}
        if p.name.startswith("/device:") and OPS_LINE in lines:
            devices.append((_events(lines[OPS_LINE]),
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
    busies = [_union(ops, lo, hi) for ops, _ in devices]
    busies = [b for b in busies if b]
    if not busies:
        raise ValueError("no device operation ran inside the window")
    busy_ns = [sum(e - s for s, e in b) for b in busies]

    ops_ns: Dict[str, float] = defaultdict(float)
    ops, mods = devices[0]
    mods = sorted(mods)
    starts = [m[0] for m in mods]
    for s, self_ns, name in _self_times(ops, lo, hi):
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
    }


def _self_times(ops: List[Span], lo: float, hi: float):
    """(start, self time, name) of each op clipped to [lo, hi]."""
    clipped = sorted(((max(s, lo), min(e, hi), n) for s, e, n in ops
                      if min(e, hi) > max(s, lo)), key=lambda x: (x[0], -x[1]))
    out, stack = [], []          # stack entries: [start, end, name, inner]
    for s, e, n in clipped:
        while stack and stack[-1][1] <= s:
            t = stack.pop()
            out.append((t[0], t[1] - t[0] - t[3], t[2]))
        if stack:
            stack[-1][3] += e - s
        stack.append([s, e, n, 0.0])
    out += [(t[0], t[1] - t[0] - t[3], t[2]) for t in stack]
    return out


def _sum_by(gaps) -> Dict[str, float]:
    out: Dict[str, float] = defaultdict(float)
    for ns, name in gaps:
        out[name] += ns
    return dict(out)


def reduce_file(path: str) -> Dict:
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(path).planes)


def reduce_dir(out_dir: str) -> Dict:
    found = glob.glob(os.path.join(out_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise ValueError(f"expected one .xplane.pb under {out_dir}, found "
                         f"{found}")
    return reduce_file(found[0])
