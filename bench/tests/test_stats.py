"""The serving metrics' arithmetic on synthetic request timestamps."""
import types

import pytest

from bench import stats


def req(arrival, times, prompt_len=10, admitted=None):
    return types.SimpleNamespace(arrival=arrival, token_times=list(times),
                                 prompt_len=prompt_len, admitted_at=admitted)


def test_ttft_counts_from_the_scheduled_arrival():
    # a request due at 1.0 whose first token came at 1.5 waited 0.5,
    # however late it was admitted
    r = req(1.0, [1.5, 1.6], admitted=1.4)
    assert stats.ttfts([r]) == [pytest.approx(0.5)]
    assert stats.queue_waits([r]) == [pytest.approx(0.4)]


def test_tbt_takes_every_gap_of_every_request():
    a = req(0.0, [0.1, 0.2, 0.4])
    b = req(0.0, [0.3, 1.3])
    assert sorted(stats.token_gaps([a, b])) == pytest.approx([0.1, 0.2, 1.0])


def test_percentile_over_all_values_not_chunk_medians():
    # 20 gaps: 19 of 10 ms and one of 1 s.  Medians of chunks of 5 would
    # never see the 1 s gap; the 95th percentile of all of them does.
    vals = [0.01] * 19 + [1.0]
    chunk_medians = [sorted(vals[i:i + 5])[2] for i in range(0, 20, 5)]
    assert max(chunk_medians) == 0.01
    assert stats.percentile(vals, 95) == pytest.approx(0.01 + 0.05 * 0.99)
    assert stats.percentile(vals, 100) == 1.0


def test_rate_is_all_tokens_over_the_whole_window():
    reqs = [req(0.0, [0.5, 1.0, 9.9]), req(2.0, [3.0, 10.5])]
    # 4 tokens inside [0, 10]; the one at 10.5 is past the window
    assert stats.tokens_in(reqs, 0.0, 10.0) == 4
    assert stats.rate(4, 10.0) == 0.4
    with pytest.raises(ValueError):
        stats.rate(4, 0.0)


def test_decode_steps_rebuilt_from_shared_timestamps():
    # two requests decoded together at 1.0 and 2.0; the first tokens came
    # from their prefills (0.5 and 0.7) and are no decode step
    a = req(0.0, [0.5, 1.0, 2.0], prompt_len=100)
    b = req(0.0, [0.7, 1.0], prompt_len=30)
    steps = stats.decode_steps([a, b])
    assert steps == [[(100, 101), (30, 31)], [(100, 102)]]
    assert stats.decode_steps([a, b], 1.5, 3.0) == [[(100, 102)]]
