"""A percentile, in ms, of the wall time of the ``Server.step`` calls that
admitted nothing and decoded something (pure decode steps)."""
import numpy as np


def decode_only(run):
    return [s for s in run.steps if s.get("admitted") == 0 and s["decoded"]]


def read(run, config, peaks, q: float):
    ts = [s["t1"] - s["t0"] for s in decode_only(run)]
    return 1e3 * float(np.percentile(ts, q)) if ts else None
