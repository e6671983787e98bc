"""The trace reduction: busy and idle, time per operation and per
program, idle gaps by host span, exposed collectives, roofline shares —
on a hand-made trace and on the small trace recorded on the chip that
is kept beside these tests."""
import json
import os

import pytest

from benchmarks import manifest, roofline
from benchmarks import trace as tr
from benchmarks.trace import Device, Op, Trace

DATA = os.path.join(os.path.dirname(__file__), 'data')
PROGRAMS = manifest.programs()
MS = 1e6   # nanoseconds


def hand_made():
    """One device, 100 ms window. Two decode chunks of 30 ms (a while
    loop holding two fusions each), a 10 ms prefill, gaps between."""
    ops = [
        Op('while.1', 0, 30 * MS, 'jit__paged_chunk_impl'),
        Op('fusion.7', 0, 20 * MS, 'jit__paged_chunk_impl',
           '%fusion.7 = bf16[24,2049,8,16,128]{4,3,2,1,0} fusion(...)'),
        Op('copy.3', 20 * MS, 10 * MS, 'jit__paged_chunk_impl',
           '%copy.3 = bf16[48,8,128]{2,1,0} copy(...)'),
        Op('fusion.9', 40 * MS, 10 * MS, 'jit_forward_cached'),
        Op('while.1', 60 * MS, 30 * MS, 'jit__paged_chunk_impl'),
        Op('fusion.7', 60 * MS, 20 * MS, 'jit__paged_chunk_impl',
           '%fusion.7 = bf16[24,2049,8,16,128]{4,3,2,1,0} fusion(...)'),
        Op('all-reduce.2', 80 * MS, 10 * MS, 'jit__paged_chunk_impl'),
    ]
    modules = [Op('jit__paged_chunk_impl(1)', 0, 30 * MS),
               Op('jit_forward_cached(2)', 40 * MS, 10 * MS),
               Op('jit__paged_chunk_impl(1)', 60 * MS, 30 * MS)]
    spans = [Op('bench.window', 0, 100 * MS),
             Op('engine.retire_chunk', 28 * MS, 14 * MS),
             Op('engine.drain_firsts', 30 * MS, 4 * MS),
             Op('engine.admit', 48 * MS, 14 * MS)]
    t = Trace([Device('/device:TPU:0', ops, modules)], spans)
    t.t0, t.t1 = tr.window_of(t)
    return t


def test_window_comes_from_the_benchmarks_own_span():
    t = hand_made()
    assert (t.t0, t.t1) == (0, 100 * MS)
    t.spans = [s for s in t.spans if s.name != 'bench.window']
    assert tr.window_of(t) == (0, 90 * MS)


def test_busy_is_the_union_of_leaf_operations():
    t = hand_made()
    busy, fullest, window = tr.busy_and_window(t)
    # 30 + 10 + 30 ms; the while loops that hold them count for nothing
    assert busy == pytest.approx(0.070) and fullest == busy
    assert window == pytest.approx(0.100)
    assert tr.gaps(t.devices[0], t.t0, t.t1) == [
        (30 * MS, 40 * MS), (50 * MS, 60 * MS), (90 * MS, 100 * MS)]


def test_union_merges_and_clips():
    assert tr.union([(0, 5), (3, 8), (10, 12), (-4, 1)], 0, 11) == \
        [(0, 8), (10, 11)]
    assert tr.union([], 0, 1) == []


def test_idle_gaps_go_to_the_innermost_open_span():
    rows = dict(tr.idle_by_span(hand_made()))
    assert rows == {'engine.drain_firsts': pytest.approx(0.010),
                    'engine.admit': pytest.approx(0.010),
                    'unattributed': pytest.approx(0.010)}


def test_operations_are_labelled_program_op_dtype_shape():
    rows = tr.per_op_seconds(hand_made(), PROGRAMS)
    assert rows[0] == ['decode/fusion_bf16_24_2049_8_16_128_',
                       pytest.approx(0.040)]
    labels = dict(rows)
    assert labels['decode/copy_bf16_48_8_128_'] == pytest.approx(0.010)
    assert labels['prefill/fusion'] == pytest.approx(0.010)
    assert not any('while' in k for k in labels)
    assert len(tr.per_op_seconds(hand_made(), PROGRAMS, top=2)) == 2


def test_time_per_program_and_per_execution():
    t = hand_made()
    secs = tr.program_seconds(t, PROGRAMS)
    assert secs['decode'] == (pytest.approx(0.060), 2)
    assert secs['prefill'] == (pytest.approx(0.010), 1)
    assert tr.module_runs(t, PROGRAMS, 'decode') == [
        pytest.approx(0.030), pytest.approx(0.030)]
    assert tr.module_runs(t, PROGRAMS, 'train_step') == []


def test_program_names_come_from_the_data_file():
    assert tr.program_of('jit__paged_chunk_impl', PROGRAMS) == 'decode'
    assert tr.program_of('jit__prefill_shared_impl', PROGRAMS) == \
        'prefill_suffix'
    assert tr.program_of('jit_forward_cached', PROGRAMS) == 'prefill'
    assert tr.program_of('jit__step', PROGRAMS) == 'train_step'
    assert tr.program_of('jit_something_new', PROGRAMS) == \
        'jit_something_new'


def test_a_collective_is_exposed_only_where_nothing_else_runs():
    t = hand_made()
    dev = t.devices[0]
    assert tr.exposed_collective_s(dev, t.t0, t.t1) == pytest.approx(0.010)
    dev.ops.append(Op('fusion.11', 84 * MS, 10 * MS,
                      'jit__paged_chunk_impl'))
    assert tr.exposed_collective_s(dev, t.t0, t.t1) == pytest.approx(0.004)


def test_json_round_trip():
    t = hand_made()
    again = tr.from_json(json.loads(json.dumps(tr.to_json(t))))
    assert tr.busy_and_window(again) == tr.busy_and_window(t)
    assert tr.per_op_seconds(again, PROGRAMS) == \
        tr.per_op_seconds(t, PROGRAMS)


def test_no_trace_no_numbers():
    assert tr.load(os.path.join(DATA, 'nothing_here')) is None
    empty = Trace([], [])
    assert tr.busy_and_window(empty) == (0.0, 0.0, 0.0)
    assert tr.idle_by_span(empty) == [] and tr.per_op_seconds(
        empty, PROGRAMS) == []


class _Ctx:
    """The little of a run's context the trace readers read."""
    def __init__(self, trace, mix, records=()):
        self.trace, self.mix, self.records = trace, mix, list(records)
        self.programs = PROGRAMS
        self.chips = 1
        self.peaks = roofline.peaks_for('TPU v5 lite')
        self.trace_t0, self.trace_t1 = 0.0, 0.1
        with open(os.path.join(manifest.HERE, 'configs',
                               'internlm2-1.8b.json')) as f:
            self.cfg = json.load(f)


MIX = {'engine': {'chunk_steps': 8, 'slots': 48}}


def test_readers_on_the_hand_made_trace():
    from benchmarks.readers import device_trace, kernel_roofline
    ctx = _Ctx(hand_made(), MIX)
    assert device_trace.read(ctx, 'idle_share') == pytest.approx(30.0)
    assert device_trace.read(ctx, 'program_ms', program='decode',
                             steps_key='chunk_steps') == \
        pytest.approx(30.0 / 8)
    assert device_trace.read(ctx, 'program_share', program='prefill') == \
        pytest.approx(100 * 10 / 70)
    assert device_trace.read(ctx, 'program_ms', program='train_step') is None
    assert device_trace.read(ctx, 'exposed_collective_share',
                             program='decode') is None     # one chip
    # no request live in the window: no bytes to rate the step by
    assert kernel_roofline.read(ctx, 'decode_step') is None
    assert kernel_roofline.read(ctx, 'flash_fwd') is None   # not in the trace


def test_decode_roofline_is_bytes_over_bandwidth_over_step_time():
    from benchmarks.readers import kernel_roofline
    from benchmarks.roofline import decode_step
    from benchmarks.timeline import Record
    recs = [Record(rid=i, prompt_len=100, max_new=50,
                   arrivals=[(-1.0, 1), (9.0, 49)]) for i in range(4)]
    ctx = _Ctx(hand_made(), MIX, recs)
    got = kernel_roofline.read(ctx, 'decode_step')
    flops, nbytes = decode_step.ops_and_bytes(ctx.cfg, 4, 4 * 101)
    want = 100 * (nbytes / 819e9) / (0.030 / 8)
    assert got == pytest.approx(want)


RECORDED = sorted(f for f in os.listdir(DATA) if f.startswith('small_trace_'))


@pytest.mark.parametrize('name', RECORDED)
def test_recorded_trace_reduces_to_sane_numbers(name):
    t = tr.load_json(os.path.join(DATA, name))
    assert t.devices and t.t1 > t.t0
    busy, fullest, window = tr.busy_and_window(t)
    assert 0 < busy <= fullest <= window * 1.0001
    ops = tr.per_op_seconds(t, PROGRAMS)
    assert 1 <= len(ops) <= 10
    assert ops == sorted(ops, key=lambda r: -r[1])
    assert sum(s for _, s in ops) <= busy * 1.0001 * max(
        1, len(t.devices))
    secs = tr.program_seconds(t, PROGRAMS)
    assert sum(v for v, _ in secs.values()) >= busy * 0.999
    known = {p['name'] for p in PROGRAMS}
    assert set(secs) & known, secs
    idle = tr.idle_by_span(t)
    assert sum(s for _, s in idle) == pytest.approx(window - busy, abs=1e-3)
