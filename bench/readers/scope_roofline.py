"""A named scope of the decode program against the chip's peak bandwidth,
in %: the bytes the decode steps of the traced part needed in that scope
(as the configuration's family module counts them,
``decode_scope_bytes``), per execution, over the scope's device time per
execution (``bench.trace_reduce``) at peak bandwidth. Nothing where the
scope did not run, no decode step fell in the traced part, or the family
counts no bytes by scope."""
from bench import program as bench_program
from bench.common import SubTrace
from bench.readers import program_device_ms


def traced_steps(run):
    """The decode steps wholly inside the traced part of the window (the
    part ``SubTrace`` records, by its own constants)."""
    t0 = SubTrace.START * run.window_s
    t1 = t0 + min(SubTrace.LENGTH * run.window_s, SubTrace.MAX_S)
    return [s for s in run.steps
            if s["decoded"] and t0 <= s["t0"] and s["t1"] <= t1]


def read(run, config, peaks, program: str, scope: str):
    ms = program_device_ms.read(run, config, peaks, program, scope)
    count = getattr(bench_program.family(config), "decode_scope_bytes",
                    None)
    steps = traced_steps(run)
    if not ms or count is None or not steps:
        return None
    need = sum(count(config, scope, s["decoded"], s["context"])
               for s in steps) / len(steps)
    return 100.0 * need / peaks["hbm_bytes_per_s"] / (ms / 1e3)
