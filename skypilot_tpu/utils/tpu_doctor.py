"""TPU doctor: the framework process table, and a backend probe.

A chip belongs to one process at a time: a leaked agent, gang supervisor
or serving replica that touched jax holds it, and every later process
that needs it fails or hangs at backend init. This module answers the
two questions that follow:

  * ``framework_processes`` snapshots every live framework daemon with
    its session fingerprint (see below), proving the process table clean
    or naming the holder; ``reap_stray_processes`` kills the ones this
    session provably owns.
  * ``probe_backend`` runs ``python -m skypilot_tpu.utils.jax_env`` — the
    entry points' own backend bring-up — as an ordinary child and
    reports the device it found, or kills it at the timeout.

Ownership fingerprinting (round-3 advisor medium): daemons spawned by a
test session or bench run inherit ``SKYTPU_SESSION_FINGERPRINT`` in
their environment; sweepers must only kill processes carrying their own
fingerprint (or an explicit test/bench tmp path in cmdline) — a
name-pattern + ppid==1 match alone may be a user's live deployment.

Reference analog: ``sky check`` plus the debugging runbook the reference
ships in ``sky/utils/controller_utils.py`` error paths.
"""
from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import time
from typing import Any, Dict, List, Optional

SESSION_ENV = 'SKYTPU_SESSION_FINGERPRINT'

# Cmdline fragments identifying the framework's own daemon entrypoints.
FRAMEWORK_PATTERNS = ('skypilot_tpu.agent', 'skytpu_gangd',
                      'SKYTPU_REPLICA_PORT', 'skypilot_tpu.serve',
                      'skypilot_tpu.jobs')

# Cmdline fragments that mark a process as disposable test/bench debris
# even without an environment fingerprint (pre-fingerprint leaks).
_EPHEMERAL_CMD_HINTS = ('/tmp/pytest-', 'skytpu-bench-')


def session_fingerprint() -> str:
    """This process's fingerprint, minting (and exporting) one if unset
    so every daemon spawned from here inherits it."""
    fp = os.environ.get(SESSION_ENV)
    if not fp:
        fp = f'{os.uname().nodename}-{os.getpid()}-{int(time.time())}'
        os.environ[SESSION_ENV] = fp
    return fp


def _read_proc(pid: int) -> Optional[Dict[str, Any]]:
    try:
        with open(f'/proc/{pid}/cmdline', 'rb') as f:
            cmd = f.read().replace(b'\0', b' ').decode(
                'utf-8', errors='replace').strip()
        with open(f'/proc/{pid}/stat', encoding='utf-8') as f:
            stat = f.read().rsplit(')', 1)[1].split()
        ppid = int(stat[1])
        starttime_ticks = int(stat[19])
    except (OSError, ValueError, IndexError):
        return None
    fingerprint = None
    try:
        # environ is readable only for same-uid processes; an unreadable
        # one must still APPEAR in the table (fingerprint unknowable →
        # treated as not-ours), or another user's daemon holding the
        # chip would be invisible to audit-clean and the diagnostics.
        with open(f'/proc/{pid}/environ', 'rb') as f:
            env_blob = f.read()
    except OSError:
        env_blob = b''
    marker = SESSION_ENV.encode() + b'='
    for pair in env_blob.split(b'\0'):
        if pair.startswith(marker):
            fingerprint = pair[len(marker):].decode('utf-8', 'replace')
            break
    try:
        hertz = os.sysconf('SC_CLK_TCK')
        with open('/proc/uptime', encoding='utf-8') as f:
            uptime = float(f.read().split()[0])
        age_s = round(uptime - starttime_ticks / hertz, 1)
    except (OSError, ValueError):
        age_s = None
    return {'pid': pid, 'ppid': ppid, 'age_s': age_s,
            'cmdline': cmd[:300], 'fingerprint': fingerprint}


def framework_processes() -> List[Dict[str, Any]]:
    """Every live process matching a framework daemon pattern, with its
    ownership fingerprint (None = not spawned by a fingerprinted
    session: possibly a real deployment — do not kill blindly)."""
    me = os.getpid()
    out = []
    for entry in os.listdir('/proc'):
        if not entry.isdigit() or int(entry) == me:
            continue
        info = _read_proc(int(entry))
        if info is None:
            continue
        if any(p in info['cmdline'] for p in FRAMEWORK_PATTERNS):
            out.append(info)
    return out


def _ancestors_of(pid: int) -> set:
    seen = set()
    while pid > 1:
        try:
            with open(f'/proc/{pid}/stat', encoding='utf-8') as f:
                pid = int(f.read().rsplit(')', 1)[1].split()[1])
            seen.add(pid)
        except (OSError, ValueError, IndexError):
            break
    return seen


def classify_strays(own_fingerprint: Optional[str] = None,
                    reap_all: bool = False):
    """Split live framework processes into (victims, spared) under the
    ownership rules of ``reap_stray_processes`` — without killing
    anything (tests exercise the policy through this)."""
    if own_fingerprint is None:
        own_fingerprint = os.environ.get(SESSION_ENV)
    ancestors = _ancestors_of(os.getpid())
    victims, spared = [], []
    for info in framework_processes():
        if info['pid'] in ancestors:
            continue
        mine = (own_fingerprint is not None
                and info['fingerprint'] == own_fingerprint)
        ephemeral = (info['fingerprint'] is not None or any(
            h in info['cmdline'] for h in _EPHEMERAL_CMD_HINTS))
        orphaned_debris = ephemeral and info['ppid'] == 1
        if mine or orphaned_debris or reap_all:
            victims.append(info)
        else:
            spared.append(info)
    return victims, spared


def reap_stray_processes(own_fingerprint: Optional[str] = None,
                         reap_all: bool = False) -> Dict[str, Any]:
    """SIGTERM→SIGKILL framework daemons this session owns.

    A victim must be provably disposable:
      * carries THIS session's fingerprint (``own_fingerprint``,
        defaulting to our ``SKYTPU_SESSION_FINGERPRINT``), or
      * carries some OTHER session's fingerprint / a test-tmp cmdline
        AND is orphaned (ppid 1) — debris whose spawning session died.
        A concurrently-running session's daemons have a live parent and
        are spared.
    Unfingerprinted matches are REPORTED in ``spared``, never killed —
    unless ``reap_all`` (explicit operator opt-in, e.g.
    ``stpu doctor --reap-all`` when a stray holds the chip).
    """
    victims, spared = classify_strays(own_fingerprint, reap_all)
    for info in victims:
        try:
            os.kill(info['pid'], signal.SIGTERM)
        except (ProcessLookupError, PermissionError):
            pass
    if victims:
        time.sleep(2.0)
        for info in victims:
            try:
                os.kill(info['pid'], signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass
    return {'reaped': victims, 'spared': spared}


# ---------------------------------------------------------------------------
# Backend probe

# Repo root (this file is skypilot_tpu/utils/tpu_doctor.py): the probe
# child is `python -m skypilot_tpu...`, which finds the package from its
# working directory.
_PKG_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def probe_backend(timeout_s: float = 90.0) -> Dict[str, Any]:
    """Bring the backend up in a child the way the entry points do
    (``utils/jax_env.init_backend``) and report the device it printed.
    The child is killed, with its process group, at ``timeout_s`` — a
    process that died holds no chip."""
    from skypilot_tpu.utils import jax_env
    t0 = time.monotonic()
    proc = subprocess.Popen(
        [sys.executable, '-m', 'skypilot_tpu.utils.jax_env'],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        cwd=_PKG_ROOT, start_new_session=True)
    try:
        out, err = proc.communicate(timeout=timeout_s)
        outcome = 'completed' if proc.returncode == 0 else 'crashed'
    except subprocess.TimeoutExpired:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        out, err = proc.communicate()
        outcome = 'timeout'
    devices = jax_env.parse_device_lines(out)
    return {'ok': outcome == 'completed' and bool(devices),
            'outcome': outcome,
            'elapsed_s': round(time.monotonic() - t0, 1),
            'timeout_s': timeout_s,
            'device': devices[-1] if devices else None,
            'stderr_tail': err[-1500:] if outcome != 'completed' else None}


def doctor_report(probe_timeout_s: float = 90.0,
                  probe: bool = True) -> Dict[str, Any]:
    """Process table plus (optionally) the backend probe, with a
    ``verdict``: a failed probe is blamed on live framework processes
    when there are any — one of them may hold the chip."""
    procs = framework_processes()
    report: Dict[str, Any] = {'framework_processes': procs}
    if probe:
        report['probe'] = probe_backend(probe_timeout_s)
        if report['probe']['ok']:
            verdict = 'backend healthy'
        elif procs:
            verdict = (f'backend init failed with {len(procs)} framework '
                       'process(es) alive — one may hold the chip; reap '
                       'them and retry')
        else:
            verdict = ('backend init failed with a clean process table; '
                       'see probe.stderr_tail')
        report['verdict'] = verdict
    return report


def main() -> int:  # `python -m skypilot_tpu.utils.tpu_doctor`
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    ap.add_argument('--timeout', type=float, default=90.0)
    ap.add_argument('--no-probe', action='store_true',
                    help='process table only (fast)')
    ap.add_argument('--reap', action='store_true',
                    help='kill fingerprinted (session-owned) strays first')
    ap.add_argument('--reap-all', action='store_true',
                    help='kill ALL framework processes (operator opt-in)')
    args = ap.parse_args()
    if args.reap or args.reap_all:
        res = reap_stray_processes(reap_all=args.reap_all)
        print(f"reaped {len(res['reaped'])}, spared {len(res['spared'])}",
              file=sys.stderr)
    report = doctor_report(args.timeout, probe=not args.no_probe)
    print(json.dumps(report, indent=2))
    if args.no_probe:
        return 0
    return 0 if report['probe']['ok'] else 1


if __name__ == '__main__':
    sys.exit(main())
