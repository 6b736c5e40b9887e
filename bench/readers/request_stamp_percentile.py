"""A percentile, in ms, of a per-request time over the finished requests:
one of the program's own stamps (``Request.queue_s``, ``Request.prefill_s``)
or the harness's first-token time from the scheduled arrival (``ttft_s``)."""
import numpy as np


def read(run, config, peaks, field: str, q: float):
    vals = [r[field] for r in run.requests if r.get(field) is not None]
    return 1e3 * float(np.percentile(vals, q)) if vals else None
