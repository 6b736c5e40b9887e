"""Runs of a cell at the program's reduced sizes on the CPU, with the look
for a chip skipped: what the fault and control tests drive."""
import copy

from bench import run as bench_run

SEED = 2 ** 31 + 4242


def tiny_cell(name: str) -> dict:
    """The cell with each part its configuration and mix have cut to a size
    the CPU runs in seconds: a serving geometry, a training batch, request
    lengths, an arrival rate."""
    c = copy.deepcopy(bench_run.cell(name))
    conf, mix = c["config"], c["mix"]
    if "serve" in conf:
        conf["serve"] = {"slots": 4, "max_len": 128}
    if "train" in conf:
        conf["train"].update(batch=4, seq=64)
    if "prompt" in mix:
        mix.update(
            prompt={"dist": "lognormal", "median": 24, "sigma": 0.7,
                    "min": 8, "max": 64, "round_to": 8},
            output={"dist": "lognormal", "median": 10, "sigma": 0.6,
                    "min": 6, "max": 32, "round_to": 1},
            check_requests=8)
    if "rate_per_s" in mix:
        mix["rate_per_s"] = 100.0
    return c


def run_tiny(name: str, seconds: float = 2.0, seed: int = SEED):
    run, _ = bench_run.execute(tiny_cell(name), seed, seconds, trace=False,
                               reduced=True)
    return run


def correct(run) -> bool:
    return all(k.ok for k in run.checks)
