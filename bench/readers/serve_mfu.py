"""Share of the chip's peak bf16 rate, in %, that the window's needed work
came to: the prefills and decode steps of every ``Server.step`` that ended
inside the window (as the configuration's family module counts it), over
the window's seconds."""
from bench import program


def read(run, config, peaks):
    steps = [s for s in run.steps if s["t1"] <= run.window_s]
    if not steps:
        return None
    fam = program.family(config)
    work = 0
    for s in steps:
        work += fam.decode_flops(config, s["decoded"], s["context"])
    work += sum(fam.prefill_flops(config, r["prompt"])
                for r in run.requests if r["first"] <= run.window_s)
    return 100.0 * work / run.window_s / peaks["bf16_flops_per_s"]
