"""Share of the chip's peak bf16 rate, in %, that the window's needed work
came to: the prefills and decode steps of every ``Server.step`` that ended
inside the window, over the window's seconds."""
from bench import flops


def read(run, config, peaks):
    steps = [s for s in run.steps if s["t1"] <= run.window_s]
    if not steps:
        return None
    work = 0
    for s in steps:
        work += flops.decode_flops(config, s["decoded"], s["context"])
    work += sum(flops.prefill_flops(config, r["prompt"])
                for r in run.requests if r["first"] <= run.window_s)
    return 100.0 * work / run.window_s / peaks["bf16_flops_per_s"]
