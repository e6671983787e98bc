"""The rest of a run, driven on the CPU at a tiny size: the harness's
look for a chip is skipped, everything after it is the real code. Sound
runs come out correct; with the timed path broken underneath, each
fault a cell can have comes out NOT correct."""
import copy
import json
import os

import numpy as np
import pytest

from benchmarks import manifest, run

DATA = os.path.join(os.path.dirname(__file__), 'data')
SERVE_LIMITS = {'gap_mean': 0.002, 'missing': 0}
TRAIN_LIMITS = {'loss_gap': 0.003, 'grad_global_gap': 0.03,
                'grad_leaf_gap': 0.03, 'change_leaf_gap': 0.1}
MANIFEST_CELLS = [w['name'] for w in manifest.benchmark()['workloads']]
CELL_OF = {'open': 'chat-steady', 'sessions': 'sessions-prefix',
           'backlog': 'tp4-batch', 'train': 'train-4k'}


def _load(name):
    with open(os.path.join(DATA, name)) as f:
        return json.load(f)


def _bench(chips=1):
    """BENCHMARK.json with every cell on ``chips``; a cell this file
    drives that the manifest does not hold (its harness is kept for a
    later PR to add by data alone) is put in for the test."""
    bench = copy.deepcopy(manifest.benchmark())
    have = {w['name'] for w in bench['workloads']}
    for name in CELL_OF.values():
        if name not in have:
            bench['workloads'].append(
                {'name': name, 'config': bench['configs'][0]['name'],
                 'traffic': name, 'chips': chips, 'why': 'test only'})
    for w in bench['workloads']:
        w['chips'] = chips  # the virtual CPU devices stand for the chips
    return bench


def tiny_run(which, seed=11, seconds=1.5, trace=False, hook=None,
             limits=None):
    cell = CELL_OF[which]
    mix = _load(f'tiny_{which}.json')
    lim = limits or (TRAIN_LIMITS if which == 'train' else SERVE_LIMITS)
    return run.run_cell(cell, seed, seconds, trace, require_chip=False,
                        bench=_bench(), cfg=_load('tiny_config.json'),
                        mix=mix, limits=lim, hook=hook)


@pytest.mark.parametrize('which', ['open', 'sessions', 'backlog', 'train'])
def test_a_sound_run_is_correct_and_prints_the_contracts_line(which):
    res = tiny_run(which, seed=2**31 + 7)
    assert list(res)[-1] == 'compared'
    assert {'correct', 'attempted', 'failed', 'metrics', 'device'} <= set(res)
    assert res['correct'] is True, res['compared']
    assert res['attempted'] > 0 and res['failed'] == 0
    assert 'setup_s' in res['metrics']
    if CELL_OF[which] in MANIFEST_CELLS:
        assert len(res['metrics']) >= 2
    if which == 'sessions':     # its TTFT tail is a per-layer metric (PERF.md)
        assert 'ttft_p90_ms' not in res['metrics']
    for name, (value, limit) in res['compared'].items():
        assert value <= limit, name
    assert res['device']['platform'] == 'cpu'      # never a device's name
    json.dumps(res)


def test_a_traced_run_reports_per_layer_metrics_and_no_device_numbers():
    res = tiny_run('sessions', trace=True)
    assert res['correct'] is True
    names = set(res['metrics'])
    assert 'kv.prefill_saved_share.tpot' in names
    assert res['metrics']['kv.prefill_saved_share.tpot']['value'] > 30
    assert res['metrics']['compile.in_window.tpot']['value'] == 0
    # no chip here: nothing read from a device trace, no share of a peak
    assert not any(n.startswith(('step.', 'device.idle', 'serve.mfu',
                                 'decode_step_roofline')) for n in names)


def _alter_tokens(run_, stage):
    """A token altered where it is produced: every third decode chunk
    hands the host other ids than the device kept."""
    if stage != 'warmed':
        return
    eng = run_.engine
    inner = eng._retire_chunk
    count = {'n': 0}

    def retire(flight, *a, **kw):
        count['n'] += 1
        if count['n'] % 3 == 0:
            flight.toks = (np.asarray(flight.toks) + 1) % 512
        return inner(flight, *a, **kw)
    eng._retire_chunk = retire


@pytest.mark.parametrize('which', ['open', 'sessions', 'backlog'])
def test_an_altered_token_is_not_correct(which):
    res = tiny_run(which, hook=_alter_tokens)
    assert res['correct'] is False
    value, limit = res['compared']['gap_mean']
    assert value > 10 * limit


def test_an_answer_that_never_comes_is_not_correct():
    def drop(run_, stage):
        if stage != 'warmed':
            return
        inner = run_.engine.submit
        seen = {'n': 0}

        def submit(row, max_new, **kw):
            seen['n'] += 1
            if seen['n'] % 5 == 0:  # the engine is asked for one token less
                return inner(row, max_new - 1, **kw)
            return inner(row, max_new, **kw)
        run_.engine.submit = submit
    res = tiny_run('open', hook=drop)
    assert res['correct'] is False
    assert res['compared']['missing'][0] >= 1
    assert res['failed'] >= 1


def _state_unchanged(run_, stage):
    """A step that returns its state unchanged (the metrics are real)."""
    if stage != 'built':
        return
    import jax
    inner = run_.step_fn

    def step(state, batch):
        kept = jax.tree.map(lambda x: x.copy(), state)
        _, metrics = inner(state, batch)
        return kept, metrics
    run_.step_fn = step


def _half_batch(run_, stage):
    """Half of the batch left out, the mean taken over the rest."""
    if stage != 'built':
        return
    inner = run_.step_fn
    run_.step_fn = lambda state, batch: inner(state, batch[:1])


@pytest.mark.parametrize('fault,number', [
    (_state_unchanged, 'change_leaf_gap'), (_half_batch, 'grad_global_gap')])
def test_a_broken_train_step_is_not_correct(fault, number):
    res = tiny_run('train', hook=fault)
    assert res['correct'] is False
    value, limit = res['compared'][number]
    assert value > limit
    if fault is _state_unchanged:
        assert value == pytest.approx(1.0, abs=1e-3)   # nothing moved


def _tp4_run(hook=None):
    """The backlog cell over four (virtual) chips at a tiny size: the
    mesh ``--tp 4`` makes, weights born sharded, the sharded step."""
    import jax
    if len(jax.devices()) < 4:
        pytest.skip('needs four devices (tests/conftest.py gives eight)')
    cell = CELL_OF['backlog']
    bench = _bench(chips=4)
    cfg = dict(_load('tiny_config.json'), num_key_value_heads=4)
    mix = _load('tiny_backlog.json')
    mix['engine']['tp'] = 4
    return run.run_cell(cell, 5, 1.5, False, require_chip=False, bench=bench,
                        cfg=cfg, mix=mix, limits=SERVE_LIMITS, hook=hook)


def test_the_sharded_cell_is_correct_over_four_devices():
    res = _tp4_run()
    assert res['correct'] is True, res['compared']
    assert res['attempted'] > 0 and res['failed'] == 0


def test_leaving_out_the_exchange_between_chips_is_not_correct():
    """Each chip keeping its own partial sum is what the output
    projections give when only the first chip's heads and feed-forward
    columns contribute: the engine is handed weights with the other
    three quarters of ``wo`` and ``w_down`` zeroed, the reference keeps
    the seed's."""
    def broken(run_, stage):
        if stage != 'built':
            return
        import jax
        p = run_.engine.params
        layers = dict(p['layers'])
        wo, wd = layers['wo'], layers['w_down']
        layers['wo'] = wo.at[:, wo.shape[1] // 4:].set(0)
        layers['w_down'] = wd.at[:, wd.shape[1] // 4:].set(0)
        run_.engine.params = jax.device_put(
            dict(p, layers=layers),
            jax.tree.map(lambda x: x.sharding, p))
    res = _tp4_run(hook=broken)
    assert res['correct'] is False
    assert res['compared']['gap_mean'][0] > 10 * SERVE_LIMITS['gap_mean']
