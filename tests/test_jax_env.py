"""utils/jax_env.py: the one compile-cache rule, the refusal of an
un-asked-for CPU, and the device block every entry point prints and
``/health`` carries. Plus the entry points themselves, started with no
accelerator and ``JAX_PLATFORMS`` unset: each must exit non-zero before
doing any work."""
import os
import pathlib
import subprocess
import sys

import jax
import pytest

from skypilot_tpu.utils import jax_env

REPO = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture()
def cache_rule(monkeypatch):
    """A fresh process as far as the rule can tell, with every
    ``jax.config.update`` it makes recorded instead of applied."""
    monkeypatch.setattr(jax_env, '_cache_state', None)
    for name in ('JAX_COMPILATION_CACHE_DIR', 'SKYTPU_COMPILE_CACHE',
                 'SKYTPU_COMPILE_CACHE_MIN_S'):
        monkeypatch.delenv(name, raising=False)
    updates = {}
    monkeypatch.setattr(jax.config, 'update',
                        lambda k, v: updates.__setitem__(k, v))
    return updates


def test_cache_env_wins_and_nothing_is_set_in_code(cache_rule, monkeypatch,
                                                   tmp_path):
    (tmp_path / 'entry').write_text('x')
    monkeypatch.setenv('JAX_COMPILATION_CACHE_DIR', str(tmp_path))
    monkeypatch.setenv('SKYTPU_COMPILE_CACHE', str(tmp_path / 'fleet'))
    state = jax_env.enable_compile_cache()
    assert cache_rule == {}  # JAX reads the variable itself
    assert state == {'enabled': True, 'dir': str(tmp_path),
                     'source': 'JAX_COMPILATION_CACHE_DIR',
                     'entries_at_start': 1, 'warm': True}
    assert not (tmp_path / 'fleet').exists()
    assert jax_env.compile_cache_state() is state


def test_cache_fleet_leaf_second(cache_rule, monkeypatch, tmp_path):
    leaf = tmp_path / 'svc-v3'
    monkeypatch.setenv('SKYTPU_COMPILE_CACHE', str(leaf))
    state = jax_env.enable_compile_cache()
    assert leaf.is_dir()
    assert cache_rule['jax_compilation_cache_dir'] == str(leaf)
    assert cache_rule['jax_persistent_cache_min_compile_time_secs'] == 0
    assert state['source'] == 'SKYTPU_COMPILE_CACHE' and not state['warm']
    # Once per process: a second call changes nothing.
    monkeypatch.setenv('SKYTPU_COMPILE_CACHE', str(tmp_path / 'other'))
    assert jax_env.enable_compile_cache() is state


def test_cache_default_is_fixed_in_the_checkout(cache_rule, monkeypatch):
    monkeypatch.delenv('JAX_PLATFORMS')
    made = []
    monkeypatch.setattr(os, 'makedirs', lambda p, **kw: made.append(p))
    state = jax_env.enable_compile_cache()
    want = str(REPO / '.jax_cache')
    assert state['dir'] == want and state['source'] == 'checkout'
    assert made == [want]
    assert cache_rule['jax_compilation_cache_dir'] == want


def test_cache_default_skips_an_asked_for_cpu(cache_rule, monkeypatch):
    monkeypatch.setenv('JAX_PLATFORMS', 'cpu')
    assert jax_env.enable_compile_cache() == {'enabled': False}
    assert cache_rule == {}


def test_cache_trouble_never_fails_a_boot(cache_rule, monkeypatch,
                                          tmp_path):
    blocker = tmp_path / 'file'
    blocker.write_text('not a directory')
    monkeypatch.setenv('SKYTPU_COMPILE_CACHE', str(blocker / 'leaf'))
    state = jax_env.enable_compile_cache()
    assert state['enabled'] is False and 'error' in state


def test_no_cache_path_from_tempfile_pid_or_clock():
    src = (REPO / 'skypilot_tpu' / 'utils' / 'jax_env.py').read_text()
    for banned in ('mkdtemp', 'import tempfile', 'getpid', 'time('):
        assert banned not in src, banned


def test_require_accelerator_raises_on_an_unasked_for_cpu(monkeypatch):
    monkeypatch.delenv('JAX_PLATFORMS')  # the suite's backend is the CPU
    with pytest.raises(RuntimeError, match='no accelerator'):
        jax_env.require_accelerator()
    monkeypatch.setenv('JAX_PLATFORMS', 'tpu,cpu')  # not "cpu": refused
    with pytest.raises(RuntimeError):
        jax_env.require_accelerator()
    monkeypatch.setenv('JAX_PLATFORMS', 'cpu')
    jax_env.require_accelerator()


def test_describe_devices_and_its_line(capsys):
    info = jax_env.describe_devices()
    assert info == {'platform': 'cpu', 'device_kind': 'cpu',
                    'device_count': len(jax.devices())}  # no CPU stats
    assert jax_env.print_device_line() == info
    log = 'noise\n' + capsys.readouterr().out + '[train] done\n'
    assert jax_env.parse_device_lines(log) == [info]


def test_health_carries_device_and_cache_state(monkeypatch):
    from skypilot_tpu.serve import llm_server
    monkeypatch.setattr(jax_env, '_cache_state', None)
    server = llm_server.LlmServer('tiny', max_len=64, engine='off')
    body = server.health_snapshot()
    assert body['device'] == jax_env.describe_devices()
    # Reading /health never configures JAX: no entry point enabled it.
    assert body['compile_cache'] == {'enabled': False}
    assert jax_env._cache_state is None


# -- the entry points, with no accelerator and JAX_PLATFORMS unset ----------


def _run(argv, platforms=None, timeout=180):
    env = {k: v for k, v in os.environ.items()
           if k not in ('JAX_PLATFORMS', 'XLA_FLAGS')}
    if platforms:
        env['JAX_PLATFORMS'] = platforms
    env['PYTHONPATH'] = str(REPO)
    return subprocess.run([sys.executable, *argv], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=timeout)


@pytest.mark.parametrize('module', ['skypilot_tpu.train.run',
                                    'skypilot_tpu.serve.llm_server'])
def test_entry_point_refuses_an_unasked_for_cpu(module):
    r = _run(['-m', module, '--model', 'tiny'])
    assert r.returncode != 0
    assert 'no accelerator' in r.stderr
    assert '[train] step' not in r.stdout  # before doing any work


def test_train_entry_point_runs_on_an_asked_for_cpu():
    r = _run(['-m', 'skypilot_tpu.train.run', '--steps', '2',
              '--log-every', '1'], platforms='cpu')
    assert r.returncode == 0, r.stderr[-2000:]
    first, last = jax_env.parse_device_lines(r.stdout)
    assert first['platform'] == last['platform'] == 'cpu'
    assert '[compile-cache] {"enabled": false}' in r.stdout
    assert r.stdout.rstrip().endswith('[train] done')


@pytest.mark.parametrize('platforms', [None, 'cpu'])
def test_bench_exits_2_with_no_tpu(platforms):
    r = _run(['bench.py'], platforms=platforms)
    assert r.returncode == 2
    assert '[bench] no TPU' in r.stderr
    assert 'metric' not in r.stdout  # nothing under the TPU metric's name
