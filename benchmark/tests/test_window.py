"""The window arithmetic: tokens at emission time, edges, due times."""

import pytest

from harness import window


def rec(times, due=0.0, sent=None, error=None):
    return {"token_times": times, "due": due, "sent": sent or due,
            "error": error}


def test_tokens_count_where_they_arrive_not_where_requests_end():
    records = [rec([9.0, 9.5, 10.0, 10.5, 11.0]),      # straddles the open
               rec([19.0, 19.9, 20.0, 20.5]),          # straddles the close
               rec([12.0, 13.0, 14.0])]                # inside, unfinished
    # [10, 20): 10.0 10.5 11.0 | 19.0 19.9 | 12 13 14  -> 8 tokens
    assert window.out_tok_s(records, 10.0, 20.0) == pytest.approx(0.8)
    # a request that completed nothing inside still counts its tokens
    assert window.out_tok_s([rec([10.0])], 10.0, 20.0) == pytest.approx(0.1)
    assert window.out_tok_s([rec([20.0])], 10.0, 20.0) == 0.0


def test_gaps_of_a_straddling_request_keep_what_lies_inside():
    r = rec([8.0, 9.0, 10.0, 11.0, 12.5, 13.0])
    assert window.request_gaps(r, 10.0, 20.0) == [1.0, 1.5, 0.5]
    assert window.request_gaps(r, 0.0, 10.0) == [1.0]       # 8 -> 9 only


def test_tpot_needs_four_gaps_and_takes_the_median_over_requests():
    fast = rec([10.0 + 0.1 * i for i in range(6)])          # mean 100 ms
    slow = rec([10.0 + 0.3 * i for i in range(6)])          # mean 300 ms
    mid = rec([10.0 + 0.2 * i for i in range(6)])           # mean 200 ms
    short = rec([10.0, 10.05, 10.1, 10.15])                 # 3 gaps: out
    value, n = window.tpot_ms([fast, slow, mid, short], 10.0, 20.0)
    assert n == 3 and value == pytest.approx(200.0)
    assert window.tpot_ms([short], 10.0, 20.0) == (None, 0)


def test_first_token_runs_from_the_due_time_over_requests_due_inside():
    records = [rec([10.4], due=10.0, sent=10.1),     # 400 ms, sent late
               rec([21.0], due=19.5),                # due inside, late token
               rec([9.9], due=9.5),                  # due before: not counted
               rec([], due=15.0, error="HTTP 503"),  # refused: the worst
               rec([], due=16.0)]                    # never answered
    times = window.first_token_ms(records, 10.0, 20.0, t_end=25.0)
    assert sorted(times) == pytest.approx([400.0, 1500.0, 9000.0, 10000.0])
    assert window.late_ms(records, 10.0, 20.0)[0] == pytest.approx(100.0)


def test_percentile_interpolates_like_numpy():
    values = [1.0, 2.0, 3.0, 4.0, 5.0]
    assert window.percentile(values, 50) == 3.0
    assert window.percentile(values, 90) == pytest.approx(4.6)
    assert window.percentile([7.0], 99) == 7.0
    with pytest.raises(ValueError):
        window.percentile([], 50)
