"""The controls of ``correct``, at the program's reduced sizes on the CPU:
the reference in a precision below the configuration's, in the program's
place, must come out not correct under the committed limits, and so must
the half-batch fault of training; the program itself, on the same run,
comes out correct. At full size they are read on the chip by
``bench/control.py`` (readings and limits in PERF.md)."""
import numpy as np
import pytest

from bench import control
from bench.tests.harness import correct, run_tiny

SEEDS = (5, 6)


@pytest.mark.parametrize("seed", SEEDS)
def test_serve_control_reads_beyond_the_program(seed):
    run = run_tiny("qwen2-1.5b.chat", seed=seed)
    lim = run.compared["limits"]
    got = control.control_serve(run, seed, lim)
    mean = "logit_gap_mean"
    assert correct(run)
    # fp8 fails the committed limit already at this size; int8's rounding
    # is finer and the tiny model's 512-token vocabulary has few near-ties,
    # so here it only reads well beyond the program (at full size on the
    # chip it fails the limit too: PERF.md)
    assert got["fp8"]["correct"] is False and got["fp8"][mean] > lim[mean]
    assert got["int8"][mean] >= 3 * max(got["program"][mean], 1e-6)


@pytest.mark.parametrize("seed", SEEDS)
def test_train_control_and_half_batch_fail_the_limits(seed):
    run = run_tiny("smollm-360m.train", seed=seed)
    got = control.control_train(run, seed, run.compared["limits"])
    assert correct(run)
    assert got["bf16"]["correct"] is False
    assert got["half_batch"]["correct"] is False
    assert np.isfinite([v for k, v in got["bf16"].items()
                        if k != "correct"]).all()
