"""Share of the traced window, in %, in which no operation ran on the
device (``bench.trace_reduce``)."""


def read(run, config, peaks):
    if not run.trace:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])
