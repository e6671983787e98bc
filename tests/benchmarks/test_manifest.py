"""BENCHMARK.json against the contract's limits, and every file a cell
or a metric needs found by name."""
import importlib
import json
import os
import re

import pytest

from benchmarks import manifest

BENCH = manifest.benchmark()
NAME = re.compile(r'^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$')
UNIT = re.compile(r'^[A-Za-z0-9_/%.\-]{1,16}$')
SOURCES = {'device_trace', 'program_span', 'program_counter', 'host_clock'}
CELLS = [w['name'] for w in BENCH['workloads']]
METRICS = BENCH['end_to_end'] + BENCH['per_layer']


def test_top_level_keys_are_exactly_the_contracts():
    assert set(BENCH) == {'command', 'paths', 'run_seconds', 'configs',
                          'workloads', 'end_to_end', 'per_layer'}
    assert 1 <= BENCH['run_seconds'] <= 51
    assert isinstance(BENCH['run_seconds'], int)
    assert BENCH['paths'] == ['benchmarks', 'tests/benchmarks']
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_run_seconds_fits_a_full_check_of_24_cells():
    runs = 2 + 14 * 24
    assert runs * (BENCH['run_seconds'] + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_command_names_no_file_outside_paths():
    assert len(BENCH['command']) <= 32
    for word in BENCH['command']:
        assert 1 <= len(word) <= 200 and '\n' not in word and '\t' not in word
        assert not word.startswith('/') and '..' not in word
    assert BENCH['command'][-1] == 'benchmarks.run'


@pytest.mark.parametrize('m', METRICS, ids=lambda m: m['name'])
def test_metric_entry_is_legal(m):
    allowed = {'name', 'unit', 'better', 'source', 'workloads'}
    allowed |= {'bound'} if m in BENCH['end_to_end'] else {'layer', 'moves'}
    assert set(m) <= allowed
    assert NAME.match(m['name']) and UNIT.match(m['unit'])
    assert m['better'] in ('lower', 'higher')
    assert m['source'] in SOURCES
    for w in m.get('workloads', []):
        assert w in CELLS
    if 'bound' in m:
        assert m['source'] in ('host_clock', 'device_trace')
        assert 0.01 <= m['bound'] <= 0.1
    else:
        assert 1 <= len(m['layer']) <= 200 and '\n' not in m['layer']
        assert m['moves'] in [e['name'] for e in BENCH['end_to_end']]


def test_names_are_unique():
    names = [m['name'] for m in METRICS]
    assert len(names) == len(set(names))
    assert len(CELLS) == len(set(CELLS))
    pairs = [(w['config'], w['traffic']) for w in BENCH['workloads']]
    assert len(pairs) == len(set(pairs))
    assert 1 <= len(BENCH['end_to_end']) <= 16
    assert 1 <= len(BENCH['per_layer']) <= 128


def test_setup_s_is_there_and_reported_everywhere():
    (setup,) = [m for m in BENCH['end_to_end'] if m['name'] == 'setup_s']
    assert 'workloads' not in setup and setup['bound'] <= 0.1


@pytest.mark.parametrize('m', BENCH['per_layer'], ids=lambda m: m['name'])
def test_each_per_layer_metrics_cells_report_what_it_moves(m):
    (moved,) = [e for e in BENCH['end_to_end'] if e['name'] == m['moves']]
    cells = m.get('workloads', CELLS)
    for c in cells:
        assert manifest.reports(moved, c), (m['name'], c, m['moves'])


@pytest.mark.parametrize('cell', CELLS)
def test_every_cell_reports_enough(cell):
    e2e = [m['name'] for m in manifest.end_to_end_for(cell, BENCH)]
    assert 'setup_s' in e2e and len(e2e) >= 2
    assert len(manifest.per_layer_for(cell, BENCH)) >= 1


@pytest.mark.parametrize('cell', BENCH['workloads'], ids=lambda w: w['name'])
def test_a_cells_files_are_found_by_name(cell):
    assert set(cell) == {'name', 'config', 'traffic', 'chips', 'why'}
    assert NAME.match(cell['name']) and NAME.match(cell['traffic'])
    assert cell['chips'] in (1, 4)
    assert 1 <= len(cell['why']) <= 200 and '\n' not in cell['why']
    cfg = manifest.config_of(cell, BENCH)
    mix = manifest.traffic_of(cell)
    assert cfg['hidden_size'] and mix['kind'] in ('serve', 'train')
    limits = manifest.limits_of(cell['name'])
    assert limits and all(isinstance(v, (int, float))
                          for v in limits.values())
    if mix['kind'] == 'serve':
        eng = mix['engine']
        assert eng['tp'] == cell['chips']
        assert eng['kv_blocks'] and eng['slots'] and eng['max_len']
        assert eng['max_len'] % eng['kv_block'] == 0


def test_at_most_a_quarter_of_the_cells_take_four_chips():
    four = sum(1 for w in BENCH['workloads'] if w['chips'] == 4)
    assert four <= max(len(CELLS) // 4, 1)


@pytest.mark.parametrize('c', BENCH['configs'], ids=lambda c: c['name'])
def test_configuration_file_gives_source_and_every_key(c):
    assert set(c) == {'name', 'source', 'file', 'reduced', 'why'}
    assert c['file'].startswith('benchmarks/configs/')
    assert c['source'].startswith('https://huggingface.co/')
    assert c['reduced'] == []
    assert any(w['config'] == c['name'] for w in BENCH['workloads'])
    with open(os.path.join(manifest.ROOT, c['file'])) as f:
        cfg = json.load(f)
    assert cfg['source'] == c['source'] and cfg['reduced'] == []
    for key in ('hidden_size', 'num_hidden_layers', 'num_attention_heads',
                'num_key_value_heads', 'intermediate_size', 'vocab_size',
                'rope_theta', 'rms_norm_eps', 'max_position_embeddings'):
        assert key in cfg
    assert cfg['hidden_size'] // cfg['num_attention_heads'] == cfg['head_dim']


@pytest.mark.parametrize('m', METRICS, ids=lambda m: m['name'])
def test_each_metric_has_a_file_and_a_reader_of_its_own(m):
    spec = manifest.metric_file(m['name'])
    for key in ('name', 'unit', 'better', 'source'):
        assert spec[key] == m[key]
    assert spec.get('workloads') == m.get('workloads')
    mod = importlib.import_module('benchmarks.readers.' + spec['reader'])
    assert callable(mod.read)
    assert isinstance(spec.get('args', {}), dict)


def test_files_under_paths_are_named_from_legal_characters():
    ok = re.compile(r'^[A-Za-z0-9_.\-/]+$')
    for path in BENCH['paths']:
        for root, dirs, files in os.walk(os.path.join(manifest.ROOT, path)):
            dirs[:] = [d for d in dirs if d != '__pycache__']
            for f in files:
                rel = os.path.relpath(os.path.join(root, f), manifest.ROOT)
                assert ok.match(rel), rel


def test_kernel_rooflines_and_the_whole_steps_mfu_stand_side_by_side():
    per = {m['name']: m for m in BENCH['per_layer']}
    for name, m in per.items():
        if name.endswith('_roofline') or '_roofline.' in name:
            assert m['unit'] == '%'
            mfus = [o for o in per.values() if 'mfu' in o['name'].split('.')
                    and o['moves'] == m['moves']]
            assert mfus, f'{name} has no mfu beside it moving {m["moves"]}'
