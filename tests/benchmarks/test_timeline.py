"""Metric arithmetic on hand-made timelines."""
import math

import pytest

from benchmarks import timeline as tl
from benchmarks.timeline import Record


def rec(rid, due, first, n, gap, max_new=None, counted=True, failed=False,
        chunk=1):
    """A request due at ``due`` whose first token lands at ``first`` and
    whose ``n`` tokens follow ``gap`` apart, ``chunk`` at a time."""
    r = Record(rid=rid, prompt_len=10, max_new=max_new or n, counted=counted,
               due=due, sent=due + 0.001, failed=failed)
    t, left = first, n
    while left > 0:
        k = min(chunk, left)
        r.arrivals.append((t, k))
        left -= k
        t += gap * k
    return r


def steady(n=20):
    """n requests a second apart, first token after 0.2 s, 61 tokens
    0.05 s apart (3 s streams, so several are open at any time)."""
    return [rec(i, float(i), i + 0.2, 61, 0.05) for i in range(n)]


def test_percentile_is_nearest_rank():
    v = list(range(1, 11))
    assert tl.percentile(v, 90) == 9
    assert tl.percentile(v, 50) == 5
    assert tl.percentile(v, 100) == 10
    assert tl.percentile([7], 90) == 7
    with pytest.raises(ValueError):
        tl.percentile([], 50)


@pytest.mark.parametrize('bad', ['failed', 'unfinished'])
def test_a_failure_ranks_above_every_finished_request(bad):
    recs = [rec(i, i, i + 0.1, 5, 0.01) for i in range(9)]
    if bad == 'failed':
        recs.append(rec(9, 9, 9.1, 5, 0.01, failed=True))
    else:
        recs.append(rec(9, 9, 9.1, 3, 0.01, max_new=5))
    m = tl.latency_metrics(recs)
    assert m['ttft_p50_ms'] == pytest.approx(100)
    assert m['ttft_p90_ms'] == pytest.approx(100)     # 9 of 10 are fine
    recs.append(rec(10, 10, 10.1, 5, 0.01, failed=True))
    m = tl.latency_metrics(recs)
    assert math.isinf(m['ttft_p90_ms']) and math.isinf(m['tpot_p90_ms'])
    assert tl.attempted_failed(recs) == (11, 2)


def test_ttft_counts_from_when_the_request_was_due():
    r = rec(0, 1.0, 1.5, 4, 0.1)
    r.sent = 1.3            # the generator ran late: the user still waited
    assert tl.ttft_s(r) == pytest.approx(0.5)
    assert tl.lateness_ms([r]) == [pytest.approx(300)]
    closed = Record(rid=1, prompt_len=1, max_new=2, sent=2.0,
                    arrivals=[(2.4, 1), (2.5, 1)])
    assert tl.ttft_s(closed) == pytest.approx(0.4)   # closed loop: from send


@pytest.mark.parametrize('chunk', [1, 4, 8])
def test_tpot_is_the_gap_a_reader_feels(chunk):
    r = rec(0, 0, 1.0, 17, 0.05, chunk=chunk)
    # (last arrival - first arrival) / (tokens - 1), stalls included
    last = r.arrivals[-1][0]
    assert tl.tpot_s(r) == pytest.approx((last - 1.0) / 16)
    assert tl.tpot_s(rec(1, 0, 1.0, 1, 0.05)) is None


def test_uncounted_requests_do_not_enter_the_tails_but_their_tokens_count():
    recs = [rec(0, 0, 0.5, 10, 0.1), rec(1, -1, 9.0, 10, 0.1, counted=False)]
    assert tl.latency_metrics(recs)['ttft_p90_ms'] == pytest.approx(500)
    assert tl.tokens_in(recs, 0, 20) == 20
    assert tl.out_tok_s(recs, 0, 20) == pytest.approx(1.0)


def test_rates_are_over_the_whole_window():
    recs = [rec(0, 0, 1.0, 10, 0.1)]
    assert tl.out_tok_s(recs, 0, 10) == pytest.approx(1.0)
    assert tl.out_tok_s(recs, 0, 1.45) == pytest.approx(5 / 1.45)
    ends = [1.0, 2.0, 3.0, 4.0, 11.0]
    assert tl.train_tok_s(ends, 0.0, 4.0, 100, 1) == pytest.approx(100.0)
    assert tl.train_tok_s(ends, 0.0, 4.0, 100, 4) == pytest.approx(25.0)
    # an idle stretch at the end of the window is still the window
    assert tl.train_tok_s(ends, 0.0, 10.0, 100, 1) == pytest.approx(40.0)


def test_a_stall_moves_every_end_to_end_metric():
    """A 2 s stall at t=5 (a compile in the window, a GC pause): the
    TTFT tail, the TPOT tail and both rates must all get worse."""
    base = steady()
    stalled = []
    for r in base:
        s = rec(r.rid, r.due, r.first, 61, 0.05)
        s.arrivals = [(t + 2.0 if t >= 5.0 else t, n) for t, n in s.arrivals]
        stalled.append(s)
    a, b = tl.latency_metrics(base), tl.latency_metrics(stalled)
    assert b['ttft_p90_ms'] > a['ttft_p90_ms'] + 1900
    assert b['tpot_p90_ms'] > a['tpot_p90_ms']           # one stream stalled
    assert tl.out_tok_s(stalled, 0, 21) < tl.out_tok_s(base, 0, 21)
    ends = [0.8 * i for i in range(1, 26)]
    late = [e + 2.0 if e >= 5 else e for e in ends]
    assert tl.train_tok_s(late, 0, 20, 8192, 1) < \
        tl.train_tok_s(ends, 0, 20, 8192, 1)


def test_live_tokens_follow_the_requests_in_flight():
    recs = [rec(0, 0, 1.0, 10, 0.1), rec(1, 0, 1.0, 10, 0.1)]
    live, active = tl.live_tokens_mean(recs, 1.0, 1.9, samples=90)
    assert active == pytest.approx(2.0)
    assert 2 * 10 < live < 2 * 20          # prompt 10 plus what was emitted
    live, active = tl.live_tokens_mean(recs, 5.0, 6.0)
    assert (live, active) == (0.0, 0.0)


def test_admit_wait_needs_the_traced_stamp():
    r = rec(0, 1.0, 1.5, 4, 0.1)
    assert tl.admit_wait_ms([r]) == []
    r.admitted = 1.2
    assert tl.admit_wait_ms([r]) == [pytest.approx(200)]
