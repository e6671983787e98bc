"""The two readers of what the engine records of itself: request
timelines (``readers/request_timeline.py``) and the loop's spans
(``readers/host_spans.py``), on hand-made records and spans, and in the
traced CPU run of a tiny cell."""
import types

import pytest
import test_run_cpu   # beside this file: its tiny cells and run

from benchmarks import manifest, run
from benchmarks.readers import host_spans, request_timeline
from benchmarks.timeline import Record
from benchmarks.trace import Op, Trace

MS = 1e6   # nanoseconds

NEW = [m for m in manifest.benchmark()['per_layer']
       if m['name'].startswith(('engine.queue_wait', 'engine.prep',
                                'engine.first_wait', 'engine.host_work',
                                'engine.blocked_share'))]


def ctx_of(records=(), trace=None):
    return run.Context(cell={}, cfg={}, mix={}, chips=1, parts={},
                       records=list(records), trace=trace)


def rec(rid, due, admit, prefill, first, timeline=True, **kw):
    """A three-token answer whose engine-side stamps are given as
    seconds after ``due``."""
    r = Record(rid=rid, prompt_len=8, max_new=3, due=due, sent=due + 0.002,
               **kw)
    r.arrivals = [(due + first, 1), (due + first + 0.1, 2)]
    line = types.SimpleNamespace(
        submit=due + 0.002, admit=due + admit, prefill=due + prefill,
        first=due + first, last=due + first + 0.1) if timeline else None
    r.future = types.SimpleNamespace(timeline=line)
    return r


def ten():
    # queue 10..100 ms, prep 1..10 ms, first_wait 20..200 ms
    return [rec(i, float(i), 0.010 * (i + 1), 0.011 * (i + 1),
                0.031 * (i + 1)) for i in range(10)]


@pytest.mark.parametrize('phase,p90,p50', [('queue_wait', 90.0, 50.0),
                                           ('prep', 9.0, 5.0),
                                           ('first_wait', 180.0, 100.0)])
def test_a_phase_is_a_percentile_over_counted_finished_requests(phase, p90,
                                                                p50):
    ctx = ctx_of(ten())
    assert request_timeline.read(ctx, phase) == pytest.approx(p90)
    assert request_timeline.read(ctx, phase, q=50) == pytest.approx(p50)
    # neither a warm-up request nor one that never finished is counted
    ctx.records.append(rec(10, 10.0, 5.0, 5.5, 9.0, counted=False))
    late = rec(11, 11.0, 5.0, 5.5, 9.0)
    late.arrivals = late.arrivals[:1]
    ctx.records.append(late)
    assert request_timeline.read(ctx, phase) == pytest.approx(p90)


def test_the_queue_wait_counts_from_when_the_request_was_due():
    r = rec(0, 1.0, 0.5, 0.6, 0.9)
    assert request_timeline.phase_ms([r], 'queue_wait') == [
        pytest.approx(500.0)]
    r.due = None            # a closed loop: from when it was sent
    assert request_timeline.phase_ms([r], 'queue_wait') == [
        pytest.approx(498.0)]


def test_the_three_waits_add_up_to_the_time_to_first_token():
    for r in ten():
        waits = sum(request_timeline.phase_ms([r], p)[0]
                    for p in request_timeline.PHASES)
        assert waits == pytest.approx((r.first - r.origin) * 1e3)


@pytest.mark.parametrize('records', [
    [], [rec(0, 0.0, 0.1, 0.2, 0.3, timeline=False)]],
    ids=['no records', 'a program that stamps no timeline'])
def test_nothing_to_read_is_none_and_never_zero(records):
    for phase in request_timeline.PHASES:
        assert request_timeline.read(ctx_of(records), phase) is None
    failed = Record(rid=1, prompt_len=8, max_new=3, failed=True)
    assert request_timeline.read(ctx_of(records + [failed]),
                                 'prep') is None


def loop_turns(wrapped=True):
    """A 100 ms window holding two loop turns of 50 ms: 2 ms admitting
    (1 ms of it a prefill group), 1 ms dispatching, then the retirement:
    1 ms draining firsts (0.5 of it waiting), 40 ms waiting for the
    chunk, 2 ms of callbacks. ``wrapped``: the traced run's outside
    wrappers open six of the names once more, a hair wider."""
    spans = [Op('bench.window', 0, 100 * MS)]
    for base in (0, 50 * MS):
        mine = [('engine.advance_prefill', 0, 0.01),
                ('engine.admit_imports', 0.01, 0.01),
                ('engine.admit', 0.1, 2), ('engine.prefill_group', 1, 1),
                ('engine.dispatch_chunk', 3, 1),
                ('engine.retire_chunk', 5, 44),
                ('engine.drain_firsts', 5.1, 1),
                ('engine.wait_firsts', 5.5, 0.5),
                ('engine.wait_chunk', 6.5, 40),
                ('engine.callbacks', 47, 2)]
        for name, at, dur in mine:
            spans.append(Op(name, base + at * MS, dur * MS))
            if wrapped and name in ('engine.admit', 'engine.prefill_group',
                                    'engine.dispatch_chunk',
                                    'engine.retire_chunk',
                                    'engine.drain_firsts'):
                spans.append(Op(name, base + (at - 0.01) * MS,
                                (dur + 0.02) * MS))
    t = Trace([], spans)
    t.t0, t.t1 = 0, 100 * MS
    return t


@pytest.mark.parametrize('wrapped', [False, True])
def test_a_same_named_nest_is_one_span_the_inner(wrapped):
    spans = host_spans.engine_spans(loop_turns(wrapped))
    assert len(spans['engine.dispatch_chunk']) == 2
    assert spans['engine.dispatch_chunk'][0] == (3 * MS, 4 * MS)
    assert len(spans['engine.retire_chunk']) == 2
    assert len(spans['engine.wait_chunk']) == 2


def test_host_work_leaves_out_the_three_waiting_spans():
    t = loop_turns(wrapped=False)
    # under a span: 0.02 + 2 + 1 + 44 = 47.02 ms a turn; waiting 40.5
    assert host_spans.read(ctx_of(trace=t), 'host_work_ms') == \
        pytest.approx(6.52)
    assert host_spans.read(ctx_of(trace=t), 'blocked_share') == \
        pytest.approx(81.0)
    # an idle loop is waiting too, and is not blocked on the device
    t.spans.append(Op('engine.idle', 49.5 * MS, 0.5 * MS))
    assert host_spans.read(ctx_of(trace=t), 'host_work_ms') == \
        pytest.approx(6.52)
    assert host_spans.read(ctx_of(trace=t), 'blocked_share') == \
        pytest.approx(81.0)


def test_spans_are_cut_to_the_traced_window():
    t = loop_turns(wrapped=False)
    t.t0, t.t1 = 25 * MS, 75 * MS       # half of each wait_chunk
    assert host_spans.read(ctx_of(trace=t), 'blocked_share') == \
        pytest.approx(100.0 * (21.5 + 19.0) / 50.0)


@pytest.mark.parametrize('spans', [
    None, [],
    [Op('engine.admit', 1 * MS, 2 * MS), Op('engine.admit', 1.1 * MS, MS),
     Op('engine.dispatch_chunk', 3 * MS, MS)]],
    ids=['no trace', 'no spans', 'only the spans put on from outside'])
def test_a_program_that_opens_no_spans_gives_nothing_to_read(spans):
    t = None
    if spans is not None:
        t = Trace([], [Op('bench.window', 0, 100 * MS)] + spans)
        t.t0, t.t1 = 0, 100 * MS
    for stat in ('host_work_ms', 'blocked_share'):
        assert host_spans.read(ctx_of(trace=t), stat) is None


@pytest.mark.parametrize('m', NEW, ids=lambda m: m['name'])
def test_each_new_metric_has_its_file_and_its_reader(m):
    spec = manifest.metric_file(m['name'])
    assert {k: spec[k] for k in m} == m
    assert spec['source'] == 'program_span' and spec['layer'] == 'scheduler'
    assert callable(manifest.reader(spec['reader']))
    twin = m['name'].endswith('.tpot')
    assert m['moves'] == ('tpot_p90_ms' if twin else 'ttft_p90_ms')


def test_there_are_eight_of_them():
    assert len(NEW) == 8


def test_the_traced_cpu_run_reads_the_engines_own_record():
    res = test_run_cpu.tiny_run('sessions', trace=True)
    assert res['correct'] is True
    got = res['metrics']
    for name in ('engine.queue_wait_p90_ms.tpot', 'engine.prep_p90_ms.tpot',
                 'engine.first_wait_p90_ms.tpot',
                 'engine.host_work_ms.tpot', 'engine.blocked_share.tpot'):
        assert name in got, sorted(got)
        assert got[name]['value'] >= 0
    assert 0 < got['engine.blocked_share.tpot']['value'] <= 100
    assert got['engine.host_work_ms.tpot']['value'] > 0
    # the TTFT twins belong to the open-loop cell
    assert 'engine.queue_wait_p90_ms' not in got
