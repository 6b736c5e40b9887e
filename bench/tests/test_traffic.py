"""The generator: the same seed gives the same requests; every seed gets
the same set of sizes and gaps, in another order."""
import itertools

import numpy as np

from bench import common, traffic
from bench.run import ROOT

CHAT = common.load_json(ROOT, "bench", "traffic", "chat.json")
LONG = common.load_json(ROOT, "bench", "traffic", "longprompt.json")
SEEDS = (3, 2 ** 31 + 5, 2 ** 40 + 1)


def _shape(plan):
    return [(round(p.due_s, 12), len(p.prompt), p.max_new) for p in plan]


def test_same_seed_same_requests():
    a = traffic.open_loop(CHAT, SEEDS[1], 40, 1000)
    b = traffic.open_loop(CHAT, SEEDS[1], 40, 1000)
    assert _shape(a) == _shape(b)
    assert all((x.prompt == y.prompt).all() for x, y in zip(a, b))


def test_every_seed_same_work_other_order():
    plans = [traffic.open_loop(CHAT, s, 40, 1000) for s in SEEDS]
    sizes = [sorted((len(p.prompt), p.max_new) for p in plan)
             for plan in plans]
    assert sorted(len(p.prompt) for p in plans[0]) == sorted(
        len(p.prompt) for p in plans[1])
    assert sorted(p.max_new for p in plans[0]) == sorted(
        p.max_new for p in plans[2])
    gaps = [np.sort(np.diff([0.0] + [p.due_s for p in plan]))
            for plan in plans]
    np.testing.assert_allclose(gaps[0], gaps[1])
    assert _shape(plans[0]) != _shape(plans[1])
    assert len(plans[0]) == round(CHAT["rate_per_s"] * 40)
    assert abs(plans[0][-1].due_s - 40) < 1e-9
    assert sizes[0] != []


def test_chat_lengths():
    plan = traffic.open_loop(CHAT, 9, 40, 1000)
    lens = np.array([len(p.prompt) for p in plan])
    outs = np.array([p.max_new for p in plan])
    assert lens.min() >= 128 and lens.max() <= 3072
    assert (lens % 128 == 0).all()
    # the medians the mix states (the trace's), prompts within one step of
    # the rounding up to 128
    assert 0 <= np.median(lens) - CHAT["prompt"]["median"] <= 128
    assert outs.min() >= 16 and outs.max() <= 1000
    assert abs(np.median(outs) - CHAT["output"]["median"]) <= 2
    assert traffic.exercised_lengths(CHAT, 40) == sorted(set(lens.tolist()))


def test_saturated_list_repeats_the_set_in_new_orders():
    gen = traffic.saturated(LONG, 4, 1000)
    first = list(itertools.islice(gen, 2 * LONG["set_size"]))
    a, b = first[: LONG["set_size"]], first[LONG["set_size"]:]
    assert sorted(len(p.prompt) for p in a) == sorted(len(p.prompt)
                                                      for p in b)
    assert [len(p.prompt) for p in a] != [len(p.prompt) for p in b]
    assert {len(p.prompt) for p in a} == set(range(2048, 3969, 128))
    assert {p.max_new for p in a} <= set(range(8, 65))
    assert len({p.uid for p in first}) == len(first)
