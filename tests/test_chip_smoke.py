"""chip_smoke.py: the CPU rehearsal runs every phase end to end (proving
the script, not the chip); without the flag it must fail where JAX finds
no TPU, and alone in a directory, printing no result either time."""
import json
import os
import pathlib
import shutil
import subprocess
import sys

REPO = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402


def _smoke(*args, cwd=REPO, script=REPO / 'chip_smoke.py', timeout=840):
    env = {k: v for k, v in os.environ.items() if k != 'PYTHONPATH'}
    r = subprocess.run([sys.executable, str(script), *args], cwd=cwd,
                       env=env, capture_output=True, text=True,
                       timeout=timeout)
    lines = [json.loads(l) for l in r.stdout.splitlines() if l.strip()]
    return r, lines


def test_rehearsal_passes_end_to_end():
    r, lines = _smoke('--rehearse-cpu')
    assert r.returncode == 0, (r.stdout[-3000:], r.stderr[-3000:])
    assert all(l['rehearsal'] is True for l in lines if 'phase' in l)
    phases = {l['phase']: l for l in lines if 'phase' in l}
    for name in ('devices', 'train-1chip', 'serve', 'kernels',
                 'launch-local', 'train-4chip-fsdp4',
                 'train-4chip-data2-tensor2', 'serve-tp4'):
        assert phases[name]['ok'] is True, phases[name]
        assert phases[name]['platform'] == 'cpu'
        assert phases[name]['device_count'] == 4
    assert set(phases) == {'devices', 'train-1chip', 'serve', 'kernels',
                           'launch-local', 'train-4chip-fsdp4',
                           'train-4chip-data2-tensor2', 'serve-tp4'}
    assert phases['serve']['prefix_hits'] > 0
    for name in ('serve', 'serve-tp4'):   # one layout, read the same way
        assert phases[name]['decode_attention'] == 'gather'   # the CPU's
    assert phases['launch-local']['status'] == 'SUCCEEDED'
    assert phases['launch-local']['framework_processes_left'] == []
    assert phases['launch-local']['gang_runner'] in ('native gangd',
                                                     'python')
    assert len(phases['kernels']['cases']) == 9
    assert sum('kda_step' in c['case']
               for c in phases['kernels']['cases']) == 1
    assert not any('flash_decode' in c['case']
                   for c in phases['kernels']['cases'])
    assert lines[-1] == {'ok': True, 'rehearsal': True, 'device': {
        'platform': 'cpu', 'kind': 'cpu', 'count': 4}}


def test_without_the_flag_it_fails_where_there_is_no_tpu():
    """The suite's environment asks for the CPU; the default run accepts
    nothing but a TPU, stops at the first child and prints no result."""
    r, lines = _smoke(timeout=300)
    assert r.returncode != 0
    assert [l['phase'] for l in lines] == ['devices']
    assert lines[0]['ok'] is False and 'rehearsal' not in lines[0]
    assert 'no tpu' in r.stderr


def test_alone_in_a_directory_it_fails(tmp_path):
    script = shutil.copy(REPO / 'chip_smoke.py', tmp_path)
    r, lines = _smoke(cwd=tmp_path, script=script, timeout=300)
    assert r.returncode != 0
    assert not any(l.get('ok') for l in lines)


def test_parsers_read_what_the_entry_points_print():
    log = ('[compile-cache] {"enabled": false}\n'
           '[device] {"platform": "tpu", "device_kind": "TPU v5 lite", '
           '"device_count": 4, "bytes_in_use": [10, 12, 11, 19]}\n'
           '[train] mesh {} over 1 slice(s)\n'
           '[train] step 1/2 loss=10.7757 step_s=41.250\n'
           '[train] step 2/2 loss=10.7001 step_s=0.780\n'
           '[train] done\n')
    assert chip_smoke.train_steps(log) == [(10.7757, 41.25), (10.7001, 0.78)]
    (device,) = chip_smoke.device_lines(log)
    assert device['platform'] == 'tpu' and device['device_count'] == 4
    assert chip_smoke.balanced(device['bytes_in_use'])
    assert not chip_smoke.balanced([100, 10, 10, 10])  # piled on chip 0
    assert not chip_smoke.balanced([10, 0, 10, 10])
    assert not chip_smoke.balanced(None)
