"""Seeded arrivals and prompts: the schedule repeats and holds its rate;
prompts repeat for a seed and change with it."""

import random

import numpy as np
import pytest
from chipbench_tiny import catalog

from chipbench import arrivals


def test_schedule_repeats_and_holds_rate():
    mix = catalog.traffic("chat")
    a = arrivals.schedule(mix, 45.0)
    assert a == arrivals.schedule(mix, 45.0)
    assert all(0 <= t < 45.0 for t, _ in a)
    assert [t for t, _ in a] == sorted(t for t, _ in a)
    rate = mix["arrival"]["rate_per_s"]
    long = arrivals.schedule(mix, 4000.0)
    assert len(long) / 4000.0 == pytest.approx(rate, rel=0.05)
    lens = [s for _, s in long]
    for s, p in mix["prompt_tokens"].items():
        assert lens.count(int(s)) / len(lens) == pytest.approx(p, abs=0.03)


def test_poisson_copy_matches_simulator():
    from repro.sim.workload import poisson_workload
    from repro.core.types import DFG, TaskSpec

    dfgs = [DFG(n, [TaskSpec("a", 0.1)], []) for n in ("x", "y", "z")]
    jobs = poisson_workload(dfgs, 2.5, 30.0, seed=9, weights=[0.2, 0.5, 0.3])
    rng = random.Random(9)
    got = arrivals.poisson_arrivals(
        rng, 2.5, 30.0, arrivals.mixture_picker(rng, dfgs, [0.2, 0.5, 0.3]))
    assert [(t, d.name) for t, d in got] == \
        [(j.arrival_time, j.dfg.name) for j in jobs]


def test_prompts_follow_seed():
    big = 2**31 + 12345
    a = arrivals.prompts([4, 16], 50280, big)
    b = arrivals.prompts([4, 16], 50280, big)
    c = arrivals.prompts([4, 16], 50280, big + 1)
    assert [x.shape for x in a] == [(1, 4), (1, 16)]
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[1], c[1])
    assert all(x.dtype == np.int32 and x.max() < 50280 for x in a)
