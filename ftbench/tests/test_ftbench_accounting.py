"""Whole-step window accounting and the kill cell's two numbers, on
synthetic series."""

import pytest

from ftbench import accounting


def _commits(step_s, n, start=100.0):
    return [start + i * step_s for i in range(n)]


def test_window_closes_at_first_commit_at_or_after_seconds():
    commits = _commits(0.94, 40)
    o, c = accounting.window_indices(commits, 5, 20.0)
    # 21 steps are 19.74 s, 22 are 20.68 s: the straddling step is taken whole
    assert (o, c) == (5, 27)
    assert commits[c] - commits[o] >= 20.0 > commits[c - 1] - commits[o]


def test_window_needs_a_commit_that_far_out():
    with pytest.raises(ValueError):
        accounting.window_indices(_commits(1.0, 10), 2, 20.0)


def test_rate_counts_only_whole_steps_between_two_commits():
    commits = _commits(0.5, 100)
    o, c = accounting.window_indices(commits, 10, 10.0)
    rate = accounting.tokens_per_s_per_chip([commits], o, c, 2048, 1)
    # steady steps: exactly tokens over step time, whatever the window's length
    assert rate == pytest.approx(2048 / 0.5)


def test_one_step_more_moves_the_rate_by_less_than_its_share():
    # a clock window would count 21 or 22 steps of 0.94 s in 20 s: 4.8 % apart.
    # between two commits, a run that takes one step more has the time of it too
    steady = _commits(0.94, 60)
    jittered = list(steady)
    for i in range(20, 60):  # one slow step shifts every later commit
        jittered[i] += 0.3
    rates = []
    for commits in (steady, jittered):
        o, c = accounting.window_indices(commits, 5, 20.0)
        rates.append(accounting.tokens_per_s_per_chip([commits], o, c, 4096, 1))
    share_of_one_step = 1.0 / 21
    assert abs(rates[0] - rates[1]) / rates[0] < share_of_one_step
    # and what it moves by is the slow step's extra time over the window
    assert rates[1] == pytest.approx(rates[0] * (22 * 0.94) / (22 * 0.94 + 0.3), rel=2e-2)


def test_rate_sums_replicas_and_divides_by_chips():
    a, b = _commits(1.0, 30), _commits(1.0, 30, start=100.2)
    two_on_one = accounting.tokens_per_s_per_chip([a, b], 3, 23, 2048, 1)
    two_by_two = accounting.tokens_per_s_per_chip([a, b], 3, 23, 4096, 4)
    assert two_on_one == pytest.approx(2 * 2048)
    assert two_by_two == pytest.approx(2 * 4096 / 4)


def test_survivor_stall_is_longest_gap_around_kill_less_median():
    commits = _commits(1.0, 10)  # 100 .. 109
    commits += [109 + 7.5, 109 + 7.5 + 2.0]  # the kill's step, then a slow one
    commits += [commits[-1] + 1.0 * i for i in range(1, 8)]
    stall = accounting.survivor_stall_s(commits, t_kill=109.3)
    assert stall == pytest.approx(7.5 - 1.0)


def test_survivor_stall_refuses_a_kill_outside_the_commits():
    with pytest.raises(ValueError):
        accounting.survivor_stall_s(_commits(1.0, 10), t_kill=50.0)


def test_resume_is_kill_to_first_commit_of_the_new_life():
    assert accounting.resume_s(200.0, [207.25, 208.0]) == pytest.approx(7.25)
    with pytest.raises(ValueError):
        accounting.resume_s(200.0, [])


def test_detect_is_kill_to_survivors_next_quorum_adopt():
    events = [
        {"name": "QUORUM_ADOPT", "t": 150.0, "world": 2},
        {"name": "QUORUM_START", "t": 200.5},
        {"name": "QUORUM_ADOPT", "t": 202.25, "world": 2, "quorum_id": 3},
        {"name": "QUORUM_ADOPT", "t": 230.0, "world": 2},
    ]
    assert accounting.detect_s(200.0, events) == pytest.approx(2.25)
    assert accounting.detect_s(300.0, events) is None


def test_union_and_quartile_spread():
    assert accounting.union_seconds([(0, 1), (0.5, 2), (3, 4)]) == pytest.approx(3.0)
    assert accounting.union_seconds([]) == 0.0
    # statistics.quantiles, exclusive method: what the driver takes
    values = [100, 101, 102, 103, 104, 105]
    assert accounting.quartile_spread(values) == pytest.approx((104.25 - 100.75) / 102.5)
