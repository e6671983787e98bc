"""tpu_doctor: fingerprint-scoped reaping (r3 advisor medium on reaper
ownership) and the backend probe — an ordinary child, killed at its
timeout."""
import os
import subprocess
import sys
import time

from skypilot_tpu.utils import tpu_doctor


def _spawn_marked(fingerprint):
    """A sleeper whose cmdline matches a framework daemon pattern; its
    environment carries (or lacks) the session fingerprint."""
    env = dict(os.environ)
    if fingerprint is None:
        env.pop(tpu_doctor.SESSION_ENV, None)
    else:
        env[tpu_doctor.SESSION_ENV] = fingerprint
    return subprocess.Popen(
        [sys.executable, '-c', 'import time; time.sleep(120)',
         'skypilot_tpu.agent.test-dummy'], env=env)


def test_framework_processes_reports_fingerprint():
    owned = _spawn_marked('fp-owned-123')
    alien = _spawn_marked(None)
    try:
        time.sleep(0.3)
        procs = {p['pid']: p for p in tpu_doctor.framework_processes()}
        assert procs[owned.pid]['fingerprint'] == 'fp-owned-123'
        assert procs[alien.pid]['fingerprint'] is None
        assert 'skypilot_tpu.agent' in procs[owned.pid]['cmdline']
    finally:
        owned.kill()
        alien.kill()
        owned.wait()
        alien.wait()


def _spawn_orphan_marked(fingerprint):
    """A marked sleeper whose spawning session has DIED (reparented to
    init): the intermediate parent exits immediately."""
    env = dict(os.environ)
    env[tpu_doctor.SESSION_ENV] = fingerprint
    script = (
        "import subprocess, sys\n"
        "p = subprocess.Popen([sys.executable, '-c',"
        " 'import time; time.sleep(120)',"
        " 'skypilot_tpu.agent.test-orphan'], start_new_session=True,"
        " stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)\n"
        "print(p.pid)\n")
    out = subprocess.run([sys.executable, '-c', script], env=env,
                         capture_output=True, text=True, timeout=30)
    return int(out.stdout.strip())


def test_reap_ownership_semantics():
    """Mine (any state) and orphaned other-session debris are reaped;
    a live concurrent session's daemons and unfingerprinted processes
    are spared (r3 advisor medium + review finding)."""
    my_fp = tpu_doctor.session_fingerprint()
    mine = _spawn_marked(my_fp)
    other_live = _spawn_marked('fp-other-session')  # parent (us) alive
    unmarked = _spawn_marked(None)
    orphan_pid = _spawn_orphan_marked('fp-dead-session')
    try:
        time.sleep(0.5)
        res = tpu_doctor.reap_stray_processes()
        reaped_pids = {p['pid'] for p in res['reaped']}
        spared_pids = {p['pid'] for p in res['spared']}
        assert mine.pid in reaped_pids  # ours: reaped
        assert orphan_pid in reaped_pids  # dead session's debris: reaped
        assert other_live.pid in spared_pids  # live peer session: spared
        assert unmarked.pid in spared_pids  # maybe a real deployment
        assert mine.wait(timeout=10) != 0
        assert other_live.poll() is None
        assert unmarked.poll() is None
        # Explicit operator opt-in classifies everything as a victim.
        # Policy-only check (classify_strays): actually issuing reap_all
        # from the suite would kill unrelated framework processes on a
        # shared host — the exact hazard this module exists to prevent.
        victims2, _ = tpu_doctor.classify_strays(reap_all=True)
        assert {other_live.pid, unmarked.pid} <= {
            p['pid'] for p in victims2}
    finally:
        for p in (mine, other_live, unmarked):
            try:
                p.kill()
                p.wait()
            except OSError:
                pass
        try:
            os.kill(orphan_pid, 9)
        except (ProcessLookupError, PermissionError):
            pass


def test_probe_backend_reports_the_device():
    # conftest pins JAX_PLATFORMS=cpu; the child inherits it, brings the
    # backend up the way the entry points do and prints its device line.
    probe = tpu_doctor.probe_backend(timeout_s=120.0)
    assert probe['ok'], probe
    assert probe['outcome'] == 'completed'
    assert probe['device']['platform'] == 'cpu'
    assert probe['device']['device_count'] >= 1
    assert probe['stderr_tail'] is None


def test_probe_backend_kills_the_child_at_its_timeout():
    """No detached child is left to finish on its own: a second process
    holding the chip is exactly what the next caller cannot survive."""
    probe = tpu_doctor.probe_backend(timeout_s=0.05)
    assert not probe['ok']
    assert probe['outcome'] == 'timeout'
    assert probe['device'] is None
    time.sleep(0.2)
    left = [p for p in tpu_doctor.framework_processes()
            if 'skypilot_tpu.utils.jax_env' in p['cmdline']]
    assert not left, left


def test_probe_backend_crash_reports_error_line(monkeypatch):
    """A clean fast failure (unknown platform, no device attached) is a
    crash — the report carries the error text."""
    monkeypatch.setenv('JAX_PLATFORMS', 'bogus-backend')
    probe = tpu_doctor.probe_backend(timeout_s=120.0)
    assert not probe['ok']
    assert probe['outcome'] == 'crashed'
    assert 'bogus' in probe['stderr_tail']


def test_probe_backend_refuses_an_unasked_for_cpu(monkeypatch):
    """JAX_PLATFORMS unset on a machine with no accelerator: JAX falls
    back to the CPU with a warning; the probe (like every entry point)
    refuses to call that healthy."""
    monkeypatch.delenv('JAX_PLATFORMS')
    probe = tpu_doctor.probe_backend(timeout_s=120.0)
    assert not probe['ok']
    assert probe['outcome'] == 'crashed'
    assert 'no accelerator' in probe['stderr_tail']


def test_doctor_report_verdict_without_probe():
    report = tpu_doctor.doctor_report(probe=False)
    assert set(report) == {'framework_processes'}  # nothing to adjudicate


def test_doctor_report_blames_live_framework_processes(monkeypatch):
    alien = _spawn_marked(None)
    monkeypatch.setenv('JAX_PLATFORMS', 'bogus-backend')
    try:
        time.sleep(0.3)
        report = tpu_doctor.doctor_report(probe_timeout_s=120.0)
    finally:
        alien.kill()
        alien.wait()
    assert not report['probe']['ok']
    assert 'may hold the chip' in report['verdict']


def test_audit_clean_tool_flags_and_clears():
    alien = _spawn_marked(None)
    try:
        time.sleep(0.3)
        r = subprocess.run([sys.executable, 'tools/audit_clean.py'],
                           capture_output=True, text=True, timeout=60)
        assert r.returncode == 1
        assert str(alien.pid) in r.stderr
        assert 'UNFINGERPRINTED' in r.stderr
    finally:
        alien.kill()
        alien.wait()
    time.sleep(0.3)
    # Scoped to our pid: the global table may legitimately hold other
    # sessions' daemons on a shared host.
    r = subprocess.run([sys.executable, 'tools/audit_clean.py'],
                       capture_output=True, text=True, timeout=60)
    assert str(alien.pid) not in r.stderr
