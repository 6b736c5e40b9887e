"""The controls of ``correct``, at the program's reduced sizes on the CPU:
the reference in a precision below the configuration's, in the program's
place, must come out not correct under the committed limits, and so must
the half-batch fault of training; the program itself, on the same run,
comes out correct. At full size they are read on the chip by
``bench/control.py`` (readings and limits in PERF.md)."""
import numpy as np
import pytest

from bench.drivers import serve, train
from bench.tests.harness import correct, run_tiny

SEEDS = (5, 6)


@pytest.mark.parametrize("seed", SEEDS)
def test_serve_control_reads_beyond_the_program(seed):
    run = run_tiny("qwen2-1.5b.chat", seed=seed)
    lim = run.compared["limits"]
    got = serve.control(run, seed, lim)
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
    got = train.control(run, seed, run.compared["limits"])
    assert correct(run)
    assert got["bf16"]["correct"] is False
    assert got["half_batch"]["correct"] is False
    assert np.isfinite([v for k, v in got["bf16"].items()
                        if k != "correct"]).all()


def test_reference_in_blocks_reads_as_in_one(monkeypatch):
    """The longprompt check runs its reference a block of sequences at a
    time; the gaps do not depend on the block."""
    run = run_tiny("qwen2-1.5b.longprompt", seed=SEEDS[0])
    whole = run.compared["gaps"]
    assert len(whole) > 3
    monkeypatch.setattr(serve, "REF_BLOCK", 3)
    blocks = serve._reference_gaps(run.compared, SEEDS[0])
    assert [len(g) for g in blocks] == [len(g) for g in whole]
    for a, b in zip(blocks, whole):
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-5)
