"""The end-to-end arithmetic of the chip benchmark: nearest-rank
percentiles and what counts as inside the measured window."""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from chipbench import window as W  # noqa: E402


@pytest.mark.parametrize("values,p,want", [
    ([5.0], 50, 5.0),
    ([3.0, 1.0, 2.0], 50, 2.0),
    ([1.0, 2.0, 3.0, 4.0], 50, 2.0),
    (list(range(1, 101)), 95, 95.0),
    (list(range(1, 101)), 100, 100.0),
    (list(range(1, 21)), 95, 19.0),
])
def test_percentile_nearest_rank(values, p, want):
    assert W.percentile(values, p) == want


def test_percentile_empty_is_nan():
    assert np.isnan(W.percentile([], 50))


class _Req:
    def __init__(self, admitted_s, output):
        self.admitted_s, self.output = admitted_s, output


def _sent(i, send, admitted, tokens, done=None, failed=False):
    s = W.Sent(i, 0, np.arange(4, dtype=np.int32), len(tokens), send)
    s.request = _Req(admitted, list(range(len(tokens))))
    s.token_s, s.done_s, s.failed = list(tokens), done, failed
    return s


def _window():
    sent = [
        # before the window: only its last gap lands inside
        _sent(0, 0.0, 0.1, [0.5, 0.9, 1.2], done=1.2),
        # wholly inside
        _sent(1, 1.0, 1.5, [2.0, 2.1, 2.3, 2.6], done=2.6),
        # first token inside, runs past the close
        _sent(2, 2.5, 3.0, [3.5, 4.0, 4.4, 5.5]),
        # sent inside, rejected
        _sent(3, 3.0, None, [], done=3.0, failed=True),
    ]
    return W.Window(sent, open_s=1.0, close_s=5.0)


def test_tokens_and_rate_take_all_the_window():
    w = _window()
    assert w.seconds == 4.0
    assert W.tokens_in(w) == 1 + 4 + 3
    assert W.tok_s(w) == pytest.approx(8 / 4.0)


def test_ttft_counts_requests_whose_first_token_is_inside():
    assert sorted(W.ttfts(_window())) == pytest.approx([1.0, 1.0])


def test_itl_pools_gaps_whose_later_token_is_inside():
    got = sorted(W.itls(_window()))
    want = sorted([0.3, 0.1, 0.2, 0.3, 0.5, 0.4])
    assert got == pytest.approx(want)


def test_queue_wait_attempted_failed_finished():
    w = _window()
    assert sorted(W.queue_waits(w)) == pytest.approx([0.5, 0.5])
    assert W.attempted(w) == 3
    assert W.failed(w) == 1
    assert [s.index for s in W.finished(w)] == [0, 1]
