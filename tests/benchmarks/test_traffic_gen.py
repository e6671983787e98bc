"""The generator: deterministic in the seed, and stratified — two
seeds offer the same work in the same time, in another order."""
import collections
import json
import os

import numpy as np
import pytest

from benchmarks import manifest
from benchmarks import traffic_gen as tg

DATA = os.path.join(os.path.dirname(__file__), 'data')
BIG_SEED = 2**31 + 12345   # the driver's seeds pass 32 signed bits


def _mix(name):
    with open(os.path.join(manifest.HERE, 'traffic', name + '.json')) as f:
        return json.load(f)


def _open_mixes():
    names = [n[:-5] for n in sorted(os.listdir(
        os.path.join(manifest.HERE, 'traffic')))]
    return [n for n in names if _mix(n).get('loop') == 'open']


@pytest.mark.parametrize('seed', [0, 1, BIG_SEED])
@pytest.mark.parametrize('name', _open_mixes())
def test_open_schedule_is_deterministic(name, seed):
    a = tg.open_schedule(_mix(name), 20, 1000, seed)
    b = tg.open_schedule(_mix(name), 20, 1000, seed)
    assert [(r.prompt, r.max_new, r.due_s) for r in a] == \
        [(r.prompt, r.max_new, r.due_s) for r in b]


@pytest.mark.parametrize('name', _open_mixes())
def test_two_seeds_offer_the_same_work(name):
    mix = _mix(name)
    a = [r for r in tg.open_schedule(mix, 30, 5000, 3) if r.counted]
    b = [r for r in tg.open_schedule(mix, 30, 5000, BIG_SEED) if r.counted]
    assert len(a) == len(b) == round(mix['rate_rps'] * 30)
    count = collections.Counter
    assert count(len(r.prompt) for r in a) == count(len(r.prompt) for r in b)
    assert count(r.max_new for r in a) == count(r.max_new for r in b)
    gaps = lambda rs: sorted(np.round(np.diff(  # noqa: E731
        [r.due_s for r in rs]), 9))
    # Gaps between arrivals are one multiset permuted: the sorted
    # half-sums of neighbours differ, so compare the gaps themselves.
    ga = sorted(tg.quantile_gaps(mix['rate_rps'], len(a), 30))
    assert np.isclose(sum(ga), 30.0)
    assert gaps(a) != gaps(b)                      # another order
    assert [r.prompt for r in a] != [r.prompt for r in b]   # other ids
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]


def test_gaps_are_one_multiset_permuted():
    mix = _mix(_open_mixes()[0])
    n = round(mix['rate_rps'] * 30)
    base = sorted(np.round(tg.quantile_gaps(mix['rate_rps'], n, 30), 9))
    for seed in (1, 2, BIG_SEED):
        rng = tg._rng(seed, 2)
        rng.permutation(n), rng.permutation(n)   # prompts, answers drawn first
        got = sorted(np.round(rng.permutation(
            tg.quantile_gaps(mix['rate_rps'], n, 30)), 9))
        assert got == base


def test_counted_requests_are_due_inside_the_window():
    mix = _mix(_open_mixes()[0])
    sched = tg.open_schedule(mix, 25, 100, 9)
    dues = [r.due_s for r in sched]
    assert dues == sorted(dues)
    for r in sched:
        assert r.counted == (0 <= r.due_s < 25)
    assert sum(1 for r in sched if r.due_s < 0) == round(
        mix['rate_rps'] * mix['ramp_s'])


@pytest.mark.parametrize('dist,spec', [
    ('lognormal', {'dist': 'lognormal', 'median': 160, 'sigma': 0.9,
                   'min': 32, 'max': 1024}),
    ('uniform', {'dist': 'uniform', 'min': 32, 'max': 128}),
    ('fixed', {'dist': 'fixed', 'value': 77, 'min': 1, 'max': 100})])
def test_quantile_lengths_follow_the_distribution(dist, spec):
    v = tg.quantile_lengths(spec, 1000)
    assert v.min() >= spec['min'] and v.max() <= spec['max']
    assert list(v) == sorted(v)
    if dist == 'lognormal':
        assert abs(np.median(v) - 160) <= 2
        assert v.max() == 1024 and v.min() == 32   # both tails reach the clip
    if dist == 'uniform':
        assert abs(v.mean() - 80) < 1
    if dist == 'fixed':
        assert set(v) == {77}


def test_unknown_distribution_is_refused():
    with pytest.raises(ValueError):
        tg.quantile_lengths({'dist': 'zipf', 'min': 1, 'max': 2}, 4)


@pytest.mark.parametrize('seed', [1, BIG_SEED])
def test_sessions_fit_and_share_what_they_should(seed):
    mix = _mix('sessions-prefix')
    s = tg.SessionScript(mix, 5000, seed)
    max_len = mix['engine']['max_len']
    for k in range(12):
        turns = s.turns(k)
        assert 1 <= len(turns) <= mix['turns']
        sys_prompt = s._system[k % mix['tenants']]
        prev = None
        for t in turns:
            assert t.prompt[:len(sys_prompt)] == sys_prompt
            assert len(t.prompt) + t.max_new <= max_len
            if prev is not None:   # history grows: the last prompt is a prefix
                assert t.prompt[:len(prev.prompt)] == prev.prompt
                covered = len(prev.prompt) // 16 * 16
                assert covered + tg.pad_width(len(t.prompt) - covered) \
                    <= max_len
            prev = t
    assert s.turns(0)[0].prompt == tg.SessionScript(
        mix, 5000, seed).turns(0)[0].prompt
    # tenants differ, sessions of one tenant differ after the system prompt
    assert s._system[0] != s._system[1]
    assert s.turns(0)[0].prompt != s.turns(mix['tenants'])[0].prompt


def test_sessions_pool_is_stratified_across_seeds():
    mix = _mix('sessions-prefix')
    a, b = tg.SessionScript(mix, 100, 1), tg.SessionScript(mix, 100, 2)
    assert sorted(a._msg) == sorted(b._msg)
    assert sorted(a._ans) == sorted(b._ans)
    assert np.allclose(sorted(a._think), sorted(b._think))
    assert list(a._msg) != list(b._msg)


def test_backlog_stream_cycles_one_stratified_pool():
    mix = _mix('batch-backlog')
    it = tg.backlog_requests(mix, 1000, 5)
    first = [next(it) for _ in range(mix['pool'])]
    again = [next(it) for _ in range(mix['pool'])]
    assert [len(r.prompt) for r in first] == [len(r.prompt) for r in again]
    assert [r.prompt for r in first] != [r.prompt for r in again]
    other = tg.backlog_requests(mix, 1000, 6)
    o = [next(other) for _ in range(mix['pool'])]
    assert sorted(len(r.prompt) for r in o) == sorted(
        len(r.prompt) for r in first)
    lo, hi = mix['prompt']['min'], mix['prompt']['max']
    assert all(lo <= len(r.prompt) <= hi for r in first)


@pytest.mark.parametrize('seed', [0, BIG_SEED])
def test_train_batches_are_seeded_and_rows_differ(seed):
    mix = _mix('train-4k')
    a = tg.train_batch(mix, 92544, seed, 0)
    assert a.shape == (mix['batch'], mix['seq_len']) and a.dtype == np.int32
    assert (a == tg.train_batch(mix, 92544, seed, 0)).all()
    assert not (a[0] == a[1]).all()
    assert not (a == tg.train_batch(mix, 92544, seed, 1)).all()
    assert a.min() >= 0 and a.max() < 92544
    assert (a == mix['separator_id']).sum() >= 2   # documents were packed


def test_pad_width_is_the_power_of_two_bucket():
    assert [tg.pad_width(n) for n in (1, 16, 17, 100, 1024, 1025)] == \
        [16, 16, 32, 128, 1024, 2048]
