import pytest

from chipbench import stats


def test_percentile_interpolates_between_closest_ranks():
    v = [4.0, 1.0, 3.0, 2.0]
    assert stats.percentile(v, 0) == 1.0
    assert stats.percentile(v, 100) == 4.0
    assert stats.percentile(v, 50) == 2.5
    assert stats.percentile(v, 95) == pytest.approx(3.85)
    assert stats.percentile([7.0], 95) == 7.0
    assert stats.percentile([], 50) is None
    with pytest.raises(ValueError):
        stats.percentile(v, 101)


def test_percentile_agrees_with_numpy():
    np = pytest.importorskip("numpy")
    v = [((i * 7919) % 1000) / 10.0 for i in range(257)]
    for q in (5, 50, 90, 95, 99):
        assert stats.percentile(v, q) == pytest.approx(np.percentile(v, q))


def test_tokens_are_counted_by_arrival_time_and_edges_are_half_open():
    times = [[0.5, 1.0, 1.5, 2.0], [1.0, 3.0], []]
    # [1.0, 2.0): 1.0 and 1.5 of the first request, 1.0 of the second
    assert stats.tokens_in_window(times, 1.0, 2.0) == 3
    # a request that began before the window and ends after it still counts
    assert stats.tokens_in_window([[0.0, 5.0, 10.0]], 4.0, 6.0) == 1
    assert stats.tokens_in_window(times, 10.0, 11.0) == 0


def test_gap_belongs_to_the_window_of_its_later_token():
    times = [[0.9, 1.1, 1.9, 2.0]]
    assert stats.gaps_in_window(times, 1.0, 2.0) == pytest.approx([0.2, 0.8])
    # first tokens close no gap; requests are never mixed
    assert stats.gaps_in_window([[1.2], [1.4]], 1.0, 2.0) == []


def test_ttft_is_timed_from_t0_for_requests_that_start_in_the_window():
    reqs = [(0.5, [0.9]),          # starts before the window
            (1.0, [1.3, 1.4]),     # on the edge: in
            (1.9, [2.6]),          # first token after the window still counts
            (2.0, [2.1]),          # starts at the end: out
            (1.5, [])]             # no token yet: left out
    assert stats.ttfts_in_window(reqs, 1.0, 2.0) == pytest.approx([0.3, 0.7])


def test_mean_live_positions_clips_to_the_window():
    # one request, prompt 10: holds 11 positions over [1, 2), 12 over [2, 4)
    reqs = [(10, [1.0, 2.0, 4.0])]
    assert stats.mean_live_positions(reqs, 1.0, 4.0) == pytest.approx(
        (11 * 1 + 12 * 2) / 3)
    assert stats.mean_live_positions(reqs, 3.0, 5.0) == pytest.approx(12 * 1 / 2)
    assert stats.mean_live_positions([(5, [1.0])], 0.0, 2.0) == 0.0
    with pytest.raises(ValueError):
        stats.mean_live_positions(reqs, 2.0, 2.0)


def test_longest_silence_counts_the_edges_of_the_window():
    times = [[1.0, 2.0, 2.5], [2.2, 6.0], [11.0]]
    assert stats.longest_silence(times, 0.0, 10.0) == pytest.approx((4.0, 6.0))
    assert stats.longest_silence(times, 2.1, 5.0) == pytest.approx((2.5, 0.4))
    assert stats.longest_silence([[]], 0.0, 3.0) == pytest.approx((3.0, 0.0))
    with pytest.raises(ValueError):
        stats.longest_silence(times, 3.0, 3.0)
