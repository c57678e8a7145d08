"""Open-loop arrival schedules and seeded prompts.

The Poisson and mixture arithmetic is copied from ``repro/sim/workload.py``
(``poisson_workload``, ``_mixture_picker``) so that a later change to the
simulator cannot move the benchmark's traffic.

The schedule (due times and prompt lengths) comes from the mix's own
``schedule_seed``: every run of a cell replays the same Poisson sample, as
a recorded trace would be replayed.  ``--seed`` draws the prompts' token
ids and the weights.  At 4/5 of capacity the JCT tails of ~100 requests
differ by about half between Poisson samples, which no bound could hold.
"""

from __future__ import annotations

import random
from typing import Callable, Dict, List, Sequence, Tuple

import numpy as np


def mixture_picker(
    rng: random.Random, items: Sequence, weights: Sequence[float]
) -> Callable[[], object]:
    total = sum(weights)
    cum = []
    acc = 0.0
    for w in weights:
        acc += w / total
        cum.append(acc)

    def pick():
        u = rng.random()
        for item, c in zip(items, cum):
            if u <= c:
                return item
        return items[-1]

    return pick


def poisson_arrivals(rng: random.Random, rate_per_s: float,
                     duration_s: float, pick: Callable[[], object]) -> List:
    """[(time, pick())] of a Poisson process over ``[0, duration_s)``, the
    draws interleaved as in ``poisson_workload``."""
    out = []
    t = 0.0
    while True:
        t += rng.expovariate(rate_per_s)
        if t >= duration_s:
            return out
        out.append((t, pick()))


def prompt_lengths(mix: Dict) -> Tuple[List[int], List[float]]:
    lens = sorted(mix["prompt_tokens"], key=int)
    return [int(s) for s in lens], [mix["prompt_tokens"][s] for s in lens]


def schedule(mix: Dict, seconds: float) -> List[Tuple[float, int]]:
    """[(due seconds after the window opens, prompt length)] over
    ``[0, seconds)``."""
    arr = mix["arrival"]
    if arr["process"] != "poisson":
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    rng = random.Random(arr["schedule_seed"])
    lens, probs = prompt_lengths(mix)
    return poisson_arrivals(rng, float(arr["rate_per_s"]), seconds,
                            mixture_picker(rng, lens, probs))


def prompts(lengths: Sequence[int], vocab: int, seed: int) -> List[np.ndarray]:
    """One (1, S) int32 prompt per request, token ids drawn from ``seed``."""
    rng = np.random.default_rng([seed, 1])
    return [rng.integers(0, vocab, size=(1, s), dtype=np.int32)
            for s in lengths]
