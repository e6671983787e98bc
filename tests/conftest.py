"""Global test fixtures.

Mirrors the reference's test strategy (SURVEY.md §4 /
``tests/common_test_fixtures.py``): unit tests run with zero cloud
credentials; multi-chip logic runs on a virtual 8-device CPU mesh
(``xla_force_host_platform_device_count``) — the fake TPU topology backend
the reference lacks.

IMPORTANT: env vars must be set before jax initializes its backends, hence
the module-level os.environ writes at import time.
"""
import os

# Force an 8-device virtual CPU platform for all tests, before jax backend
# init (backends are not yet initialized when conftest loads): the suite
# never needs, and must never take, the chip.
os.environ['JAX_PLATFORMS'] = 'cpu'
_flags = os.environ.get('XLA_FLAGS', '')
if '--xla_force_host_platform_device_count' not in _flags:
    os.environ['XLA_FLAGS'] = (
        _flags + ' --xla_force_host_platform_device_count=8').strip()

import jax

jax.config.update('jax_platforms', 'cpu')

# Ownership fingerprint for every daemon this session spawns (nohup'd
# agents, gangd, replicas all inherit the environment): the sessionfinish
# sweep and `stpu doctor --reap` kill ONLY fingerprinted processes — a
# name-pattern + ppid==1 match alone may be a user's live deployment (r3
# advisor medium). An xdist worker inherits the controller's environment,
# and with it the controller's fingerprint: each worker takes its own,
# or the first worker to finish its session would sweep the live
# daemons of the others (it did: the API servers of test_load.py and
# test_users_rbac.py, whose command line matches 'skypilot_tpu.serve').
_fingerprint = (f'pytest-{os.uname().nodename}-{os.getpid()}-'
                f'{int(__import__("time").time())}')
if os.environ.get('PYTEST_XDIST_WORKER'):
    os.environ['SKYTPU_SESSION_FINGERPRINT'] = _fingerprint
else:
    os.environ.setdefault('SKYTPU_SESSION_FINGERPRINT', _fingerprint)

# Keep black-box incident bundles out of the operator's real spool:
# engine tests legitimately trip _fail_everything (stop with live work,
# injected faults) and each trip dumps a bundle to the spool dir.
os.environ.setdefault(
    'SKYTPU_BLACKBOX_DIR',
    os.path.join(__import__('tempfile').gettempdir(),
                 f'skytpu-test-blackbox-{os.getpid()}'))

# Same rationale for the trace export spool: tail-based retention
# durably exports keep-* files for every verdict-kept trace (errors and
# slow requests that tests produce on purpose), which must not land in
# — or be read back from — the operator's real ~/.skypilot_tpu/traces.
os.environ.setdefault(
    'SKYTPU_TRACE_EXPORT_DIR',
    os.path.join(__import__('tempfile').gettempdir(),
                 f'skytpu-test-traces-{os.getpid()}'))

import pytest

# Suite tiers for CI (`make test-fast` < 5 min): modules dominated by jax
# compiles or real process orchestration are `slow`; sustained load/chaos
# suites are `load`. Everything else runs in the default fast selection.
_SLOW_MODULES = {
    'test_agent_rpc', 'test_api_server', 'test_e2e_launch', 'test_examples',
    'test_engine', 'test_engine_paged', 'test_engine_spec',
    'test_generate', 'test_grpc_exec',
    'test_ha_controllers',
    'test_k8s_e2e', 'test_lora',
    'test_managed_jobs', 'test_model_and_trainer', 'test_native_gang',
    'test_ops_attention', 'test_parallel', 'test_pipeline_moe',
    'test_oauth_login', 'test_remote_control', 'test_sampling_semantics',
    'test_serve', 'test_serve_ha', 'test_slurm_cloud',
    'test_speculative',
    'test_ssh_path', 'test_storage_and_checkpoint', 'test_token_dataset',
}
_LOAD_MODULES = {'test_load'}


def pytest_collection_modifyitems(config, items):
    del config
    for item in items:
        mod = item.module.__name__.rsplit('.', 1)[-1]
        if mod in _LOAD_MODULES:
            item.add_marker(pytest.mark.load)
        elif mod in _SLOW_MODULES:
            item.add_marker(pytest.mark.slow)


@pytest.fixture()
def tmp_state_dir(tmp_path, monkeypatch):
    """Isolate on-disk state (cluster DB, logs) per test."""
    monkeypatch.setenv('SKYTPU_STATE_DIR', str(tmp_path / 'state'))
    yield tmp_path / 'state'


@pytest.fixture(autouse=True)
def _reset_trace_tail_store(tmp_path, monkeypatch):
    """Tail-based trace retention keeps records in a process-global
    store and a durable keep-* spool (that persistence is the feature)
    — but across tests it leaks one suite's retained traces into
    another's incident bundles and /debug payloads. Same isolation
    rationale as pointing the blackbox spool at a tmp dir: per-test
    export dir, per-test retained-store reset."""
    monkeypatch.setenv('SKYTPU_TRACE_EXPORT_DIR',
                       str(tmp_path / 'trace-exports'))
    yield
    from skypilot_tpu.observability import trace as trace_lib
    # Drain queued keep exports BEFORE the env reverts, so a late
    # background write cannot land in the next test's spool.
    trace_lib.flush_keep_exports(timeout=5)
    trace_lib._TAIL.reset()


@pytest.fixture()
def enable_fake_cloud(monkeypatch, tmp_state_dir):
    """Analog of the reference's `enable_all_clouds` fixture
    (common_test_fixtures.py:176): make the `fake` cloud report valid
    credentials so the optimizer/backend can run without any real cloud."""
    monkeypatch.setenv('SKYTPU_ENABLE_FAKE_CLOUD', '1')
    from skypilot_tpu.provision.fake import instance as fake_instance
    fake_instance.reset_state()
    yield


# --- fake-ssh rig (shared by test_ssh_path + test_remote_control) ----------
# There is no sshd in the sandbox: an ``ssh`` shim installed first on PATH
# emulates a remote host — validates key/options, refuses while the host is
# "down", records every invocation, then executes the command locally under
# the host's private HOME. Real ``rsync`` runs against it via ``-e ssh``, so
# the full argv path is exercised; only the TCP/auth legs are faked.

FAKE_SSH_SHIM = r'''#!/usr/bin/env python3
import json, os, subprocess, sys

args = sys.argv[1:]
opts, key, port = [], None, None
i = 0
while i < len(args):
    a = args[i]
    if a == '-o':
        opts.append(args[i + 1]); i += 2
    elif a in ('-p', '-P'):
        port = args[i + 1]; i += 2
    elif a == '-i':
        key = args[i + 1]; i += 2
    elif a == '-N':
        i += 1
    else:
        break
dest = args[i]; i += 1
cmd_words = args[i:]
root = os.environ['FAKE_SSH_ROOT']
user, _, host = dest.partition('@')
record = {'host': host, 'user': user, 'opts': opts, 'key': key,
          'cmd': cmd_words}
with open(os.path.join(root, 'calls.jsonl'), 'a') as f:
    f.write(json.dumps(record) + '\n')
if not os.path.exists(os.path.join(root, host + '.up')):
    sys.exit(255)  # host still booting
if key is not None and not os.path.exists(os.path.expanduser(key)):
    sys.exit(255)  # auth failure
home = os.path.join(root, 'homes', host)
os.makedirs(home, exist_ok=True)
env = dict(os.environ)
env['HOME'] = home
line = ' '.join(cmd_words)  # ssh semantics: words joined, remote shell
r = subprocess.run(['bash', '-c', line], env=env, cwd=home)
sys.exit(r.returncode)
'''


@pytest.fixture()
def fake_ssh(tmp_path, monkeypatch, tmp_state_dir):
    import json as _json
    import signal as _signal
    import stat as _stat

    root = tmp_path / 'fake-ssh'
    root.mkdir()
    (root / 'homes').mkdir()
    bindir = tmp_path / 'shim-bin'
    bindir.mkdir()
    shim = bindir / 'ssh'
    shim.write_text(FAKE_SSH_SHIM)
    shim.chmod(shim.stat().st_mode | _stat.S_IEXEC)
    monkeypatch.setenv('PATH', f'{bindir}:{os.environ["PATH"]}')
    monkeypatch.setenv('FAKE_SSH_ROOT', str(root))

    class Rig:
        def __init__(self):
            self.root = root

        def up(self, host):
            # A host's login shells (`bash -lc`, the real-SSH invocation
            # path) reset PATH from /etc/profile; on a real node `ssh`
            # lives in the standard PATH, here the shim dir must be
            # restored by the profile.
            home = root / 'homes' / host
            home.mkdir(parents=True, exist_ok=True)
            (home / '.profile').write_text(
                f'export PATH={bindir}:$PATH\n')
            (root / f'{host}.up').touch()

        def calls(self):
            path = root / 'calls.jsonl'
            if not path.exists():
                return []
            return [_json.loads(l) for l in path.read_text().splitlines()]

        def home(self, host):
            return root / 'homes' / host

    yield Rig()

    # Daemons nohup'd inside fake homes (head agents, worker agents)
    # outlive monkeypatch: kill anything that recorded a pidfile.
    for pidfile in root.glob('homes/*/.skytpu/runtime/*.pid'):
        try:
            os.kill(int(pidfile.read_text().strip()), _signal.SIGTERM)
        except (ValueError, ProcessLookupError, PermissionError):
            pass
    from skypilot_tpu.agent import remote as remote_lib
    for name in list(remote_lib._conns):  # pylint: disable=protected-access
        remote_lib.drop_connection(name)


def pytest_sessionfinish(session, exitstatus):
    """Backstop sweep for leaked framework daemons (nohup'd agents, gang
    supervisors, serving replicas). Per-fixture teardown handles the
    normal case; this catches failures/interruptions mid-fixture. A
    leaked daemon is worse than untidy: a chip belongs to one process,
    so one stray that touched jax holds it against every later one.

    Ownership is proven, not guessed (r3 advisor medium): a victim must
    carry THIS session's SKYTPU_SESSION_FINGERPRINT in its environment,
    or reference this session's tmp basedir in its cmdline. A user's
    live deployment (also nohup'd, also reparented to init) matches
    neither and is left alone.
    """
    del exitstatus
    import signal

    from skypilot_tpu.utils import tpu_doctor
    my_fp = os.environ.get('SKYTPU_SESSION_FINGERPRINT')
    try:
        mybase = str(session.config._tmp_path_factory.getbasetemp())
    except Exception:
        mybase = None
    for info in tpu_doctor.framework_processes():
        ours = (my_fp is not None and info['fingerprint'] == my_fp) or \
            (mybase is not None and mybase in info['cmdline'])
        if not ours:
            continue
        try:
            os.kill(info['pid'], signal.SIGTERM)
        except (ProcessLookupError, PermissionError):
            pass
