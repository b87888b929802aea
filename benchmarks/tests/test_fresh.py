"""Send log + poll log → fresh_lag_ms, on a synthetic log with a stall."""

from lib import fresh


def _logs():
    # a marker every 50 ms from t=0; seq n leaves at 0.05 * n
    markers = [(n, 0.05 * n) for n in range(1, 601)]
    # ticks close at 5, 10, 15 (stalled: published 2 s late), 20, 25
    # each snapshot holds the markers sent up to 0.1 s before its publish
    publishes = [(1, 5.3), (2, 10.3), (3, 17.0), (4, 20.3), (5, 25.3)]
    polls = []
    t, tick, gauge = 4.0, 0, 60
    while t < 30.0:
        for k, (tk, tp) in enumerate(publishes):
            if t >= tp and tk > tick:
                tick, gauge = tk, int((tp - 0.1) / 0.05)
        polls.append((tick, gauge, t))
        t += 0.02
    return markers, polls


def test_lag_per_tick_and_mean():
    markers, polls = _logs()
    lags = dict(fresh.tick_lags(markers, polls, 4.5, 27.0))
    assert sorted(lags) == [1, 2, 3, 4, 5]
    for tk, lag in lags.items():
        # publish - newest marker: 0.1 s, plus poll (20 ms) and marker
        # (50 ms) quantisation
        assert 0.1 <= lag <= 0.1 + 0.05 + 0.02 + 1e-9, (tk, lag)
    s = fresh.summary(markers, polls, 4.5, 27.0)
    assert s["ticks"] == 5
    assert abs(s["fresh_lag_ms"] - 1e3 * sum(lags.values()) / 5) < 1e-6
    assert s["fresh_lag_max_ms"] == 1e3 * max(lags.values())


def test_stall_shows_as_old_marker():
    """A snapshot that holds only old markers (the fold stalled 2 s
    before the publish) reads 2 s stale, whenever it is seen."""
    markers = [(n, 0.05 * n) for n in range(1, 401)]
    polls = [(0, 10, 4.0), (1, 100, 5.3), (2, 160, 10.3)]
    lags = dict(fresh.tick_lags(markers, polls, 0.0, 20.0))
    assert abs(lags[1] - (5.3 - 5.0)) < 1e-9
    assert abs(lags[2] - (10.3 - 8.0)) < 1e-9      # marker 160 left at 8 s


def test_window_edges_and_first_answer():
    markers, polls = _logs()
    # the first answer of a log shows a snapshot of unknown age: no sample
    assert 0 not in dict(fresh.tick_lags(markers, polls, 0.0, 30.0))
    # only snapshots first seen inside [t0, t1) count
    assert sorted(dict(fresh.tick_lags(markers, polls, 9.0, 18.0))) == [2, 3]
    assert fresh.summary(markers, polls, 28.0, 29.0) == {}
