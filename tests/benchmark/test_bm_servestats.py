"""Window arithmetic on hand-made event lists."""

import pytest

from benchmark import servestats as st


def _it(t0, t1, landed):
    return {"t0": t0, "t1": t1, "landed": landed}


ITS = [_it(0.0, 1.0, {1: 1}),            # before the window
       _it(1.0, 2.0, {1: 1, 2: 1}),
       _it(2.0, 3.0, {1: 1, 2: 1, 3: 1}),
       _it(3.0, 5.0, {2: 1, 3: 1}),
       _it(5.0, 6.5, {3: 2})]            # ends after the close


def test_rate_counts_whole_iterations_over_their_boundaries():
    # iterations 2..4 lie inside [1, 6]: 2 + 3 + 2 tokens over 1.0 -> 5.0
    assert st.output_token_rate(ITS, 1.0, 6.0) == pytest.approx(7 / 4.0)
    assert len(st.inside(ITS, 1.0, 6.0)) == 3
    with pytest.raises(ValueError):
        st.output_token_rate(ITS, 10.0, 11.0)


def test_tpot_per_request_and_censoring():
    times = st.token_times(ITS, 1.0, 6.0)
    assert times == {1: [2.0, 3.0], 2: [2.0, 3.0, 5.0], 3: [3.0, 5.0]}
    assert sorted(st.tpot_ms(times)) == pytest.approx([1000.0, 1500.0,
                                                       2000.0])
    # one token inside the window: no gap to measure
    assert st.tpot_ms({7: [1.0]}) == []
    # several tokens landed by one iteration share its boundary
    assert st.tpot_ms({8: [1.0, 2.0, 2.0]}) == pytest.approx([500.0])


def test_ttft_from_due_time_and_censored_at_the_close():
    due = {1: 1.0, 2: 2.0, 3: 5.5, 4: 0.5, 5: 9.0}
    first = {1: 1.4, 2: 7.0, 4: 0.9}
    got = st.ttft_ms(due, first, 1.0, 6.0)
    # 1: answered inside; 2: first token after the close -> wait so far;
    # 3: no token yet -> wait so far; 4, 5: not due inside the window
    assert sorted(got) == pytest.approx([400.0, 500.0, 4000.0])


@pytest.mark.parametrize("q,want", [(0, 1.0), (50, 2.5), (90, 3.7),
                                    (100, 4.0)])
def test_percentile_interpolates(q, want):
    assert st.percentile([4.0, 1.0, 3.0, 2.0], q) == pytest.approx(want)
