"""Whole decode step against one bound of the chip, in %: the time the
bound allows for what the pure decode steps needed (``bench.flops``),
summed, over their summed wall time. ``bound`` is ``hbm`` (bytes over peak
bandwidth) or ``flops`` (operations over peak bf16 rate)."""
from bench import flops
from bench.readers.step_time_percentile import decode_only


def read(run, config, peaks, bound: str):
    steps = decode_only(run)
    if not steps:
        return None
    if bound == "hbm":
        need = sum(flops.decode_bytes(config, s["decoded"], s["context"])
                   for s in steps) / peaks["hbm_bytes_per_s"]
    else:
        need = sum(flops.decode_flops(config, s["decoded"], s["context"])
                   for s in steps) / peaks["bf16_flops_per_s"]
    return 100.0 * need / sum(s["t1"] - s["t0"] for s in steps)
