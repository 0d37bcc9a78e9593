"""The one generator of search traffic.  A mix is a data file,
`bench/traffic/<name>.json`:

    loop      "closed": a client starts its next search when its last one
              has finished
    clients   1: one designer
    round     the seeds of the searches in one round

A search's work depends on its seed (hardware probes that some layer cannot
map end their inner searches early, and the stacks narrow), so every run
does the same searches: set-up runs the round once in the listed order,
which compiles or loads every program the window uses, and the window runs
as many whole rounds as fit in `--seconds` (at least one), each in an order
drawn from the run's `--seed`.
"""

from __future__ import annotations

import json
import time

import numpy as np


def load(path: str) -> dict:
    with open(path) as f:
        mix = json.load(f)
    if mix.get("loop") != "closed" or mix.get("clients") != 1:
        raise ValueError(f"{path}: only a closed loop of one client is "
                         f"generated, got loop={mix.get('loop')!r} "
                         f"clients={mix.get('clients')!r}")
    seeds = mix.get("round")
    if (not isinstance(seeds, list) or not seeds
            or not all(isinstance(s, int) and s >= 0 for s in seeds)
            or len(set(seeds)) != len(seeds)):
        raise ValueError(f"{path}: round must list distinct seeds >= 0")
    return mix


def rounds(mix: dict, seed: int):
    """Endless rounds of the window: the round's seeds, each time in a new
    order drawn from `seed`."""
    rng = np.random.default_rng(seed)
    while True:
        yield [mix["round"][i] for i in rng.permutation(len(mix["round"]))]


def closed_loop(run_one, rounds, seconds: float, clock=time.perf_counter):
    """Run whole rounds of whole searches back to back: the first always,
    then each further round that would end within `seconds`, judged by the
    last round's duration (every round is the same work).
    `run_one(i, search_seed)` runs the window's i-th search.  Returns the
    window's start and each search's end."""
    start = clock()
    ends = []
    for searches in rounds:
        begun = clock()
        for seed in searches:
            run_one(len(ends), seed)
            ends.append(clock())
        if 2 * ends[-1] - begun - start > seconds:
            return start, ends
    return start, ends
