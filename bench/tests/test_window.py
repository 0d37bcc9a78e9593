"""The window rule behind `search_s`, on a clock the test advances."""

import json

import pytest

import traffic


class Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def run_window(durations, seconds, mix):
    clock = Clock()
    ran = []

    def one(i, seed):
        ran.append((i, seed))
        clock.t += durations[seed]

    start, ends = traffic.closed_loop(one, traffic.rounds(mix, 7), seconds,
                                      clock)
    return start, ends, ran


MIX = {"loop": "closed", "clients": 1, "round": [3, 5, 9]}
DURATIONS = {3: 10.0, 5: 20.0, 9: 30.0}


@pytest.mark.parametrize("seconds,n_rounds", [(119.0, 1), (120.0, 2),
                                              (181.0, 3)])
def test_as_many_whole_rounds_as_fit(seconds, n_rounds):
    # A round takes 60 s; another starts only if it would end in time.
    start, ends, ran = run_window(DURATIONS, seconds, MIX)
    assert start == 100.0
    assert len(ends) == 3 * n_rounds
    assert [i for i, _ in ran] == list(range(3 * n_rounds))
    for r in range(n_rounds):
        assert sorted(s for _, s in ran[3 * r:3 * r + 3]) == [3, 5, 9]
    search_s = (ends[-1] - start) / len(ends)
    assert search_s == pytest.approx(20.0)


@pytest.mark.parametrize("seconds", [0.0, 1.0])
def test_a_round_longer_than_the_window_still_runs_once(seconds):
    start, ends, _ = run_window(DURATIONS, seconds, MIX)
    assert len(ends) == 3
    assert ends[-1] - start == pytest.approx(60.0)


def test_order_is_drawn_from_the_seed():
    a = [next(traffic.rounds(MIX, s)) for s in (1, 1, 2**31 + 5)]
    assert a[0] == a[1]
    assert all(sorted(r) == [3, 5, 9] for r in a)
    big = traffic.rounds(MIX, 2**33 + 1)
    assert len({tuple(next(big)) for _ in range(20)}) > 1


@pytest.mark.parametrize("bad", [
    {"loop": "open", "clients": 1, "round": [1]},
    {"loop": "closed", "clients": 2, "round": [1]},
    {"loop": "closed", "clients": 1, "round": []},
    {"loop": "closed", "clients": 1, "round": [1, 1]},
    {"loop": "closed", "clients": 1, "round": [-1]},
])
def test_malformed_mix_is_refused(tmp_path, bad):
    path = tmp_path / "mix.json"
    path.write_text(json.dumps(bad))
    with pytest.raises(ValueError):
        traffic.load(str(path))
