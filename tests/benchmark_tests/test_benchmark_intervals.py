"""The arithmetic of a window: the rate is all of its steps over all of its
wall, so a stall lowers it by what it cost; the median interval and the
stall share stand beside it."""
import pytest

from benchmark import intervals


def _stamps(ivals, t0=100.0):
    out = [t0]
    for iv in ivals:
        out.append(out[-1] + iv)
    return out


def test_a_clean_window():
    clean = intervals.summarize(_stamps([2.0] * 12), 40960, 1)
    assert clean["train_tokens_per_s"] == pytest.approx(20480.0)
    assert clean["step_interval_s"] == 2.0
    assert clean["stall_pct"] == pytest.approx(0.0, abs=1e-9)
    assert clean["n_intervals"] == 12 and clean["wall_s"] == 24.0


@pytest.mark.parametrize("stall_s", [0.5, 2.0, 5.0])
def test_a_stall_lowers_the_rate_by_what_it_cost(stall_s):
    ivals = [2.0] * 11 + [2.0 + stall_s]
    got = intervals.summarize(_stamps(ivals), 40960, 1)
    wall = 24.0 + stall_s
    assert got["train_tokens_per_s"] == pytest.approx(40960 * 12 / wall)
    assert got["train_tokens_per_s"] < 20480.0
    # beside it: the median does not move, the stall share is the stall
    assert got["step_interval_s"] == 2.0
    assert got["stall_pct"] == pytest.approx(100 * stall_s / wall)
    assert got["max_interval_s"] == 2.0 + stall_s


def test_where_the_stall_falls_does_not_matter():
    first = intervals.summarize(_stamps([4.0] + [2.0] * 11), 40960, 1)
    last = intervals.summarize(_stamps([2.0] * 11 + [4.0]), 40960, 1)
    assert first["train_tokens_per_s"] == pytest.approx(
        last["train_tokens_per_s"])


def test_rate_is_per_chip():
    one = intervals.summarize(_stamps([3.0] * 10), 4 * 40960, 4)
    assert one["train_tokens_per_s"] == pytest.approx(40960 / 3.0)


@pytest.mark.parametrize("n, ok", [(9, False), (10, True)])
def test_fewer_than_ten_intervals_fails_the_run(n, ok):
    if ok:
        assert intervals.summarize(
            _stamps([2.0] * n), 40960, 1)["n_intervals"] == n
    else:
        with pytest.raises(intervals.TooFewIntervals):
            intervals.summarize(_stamps([2.0] * n), 40960, 1)
