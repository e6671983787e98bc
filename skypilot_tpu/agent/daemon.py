"""Per-cluster daemon: autostop enforcement + heartbeat.

Reference analog: ``sky/skylet/skylet.py`` periodic events — specifically
``AutostopEvent`` (``skylet/events.py:161``) and ``autostop_lib``'s
last-active tracking.  One daemon process per cluster, spawned at first
launch; it watches the job table for idleness and executes the recorded
autostop policy (stop or down) against the provider.

Each tick also ships a heartbeat into the cluster table
(``global_user_state.record_heartbeat``): host health (disk, framework
process count — the same /proc probes ``utils/tpu_doctor`` uses), job
progress counts, and the newest trainer-telemetry window
(``observability/train_telemetry``), so the controller and `stpu status`
see *progress*, not just liveness. The daemon must never import jax —
a chip belongs to one process, and it is the job's.

``check_once`` / ``heartbeat_once`` are pure steps (read state, maybe
act) so tests drive them synchronously without a process.
"""
from __future__ import annotations

import argparse
import json
import os
import time
from typing import Optional

from skypilot_tpu import exceptions, global_user_state
from skypilot_tpu.agent import constants, job_lib
from skypilot_tpu.observability import blackbox


def _runtime_dir(cluster_name: str) -> str:
    from skypilot_tpu.backends.tpu_gang_backend import runtime_dir
    return runtime_dir(cluster_name)


def _idle_seconds(cluster_name: str) -> Optional[float]:
    """Seconds since the last job activity; None while a job is active.

    Remote-control clusters keep their job table on the HEAD: idleness is
    judged through the agent (an unreachable head yields None — never
    stop/down a cluster on missing data)."""
    record = global_user_state.get_cluster(cluster_name)
    jobs = None
    if record is not None and record.get('handle'):
        from skypilot_tpu.backends import ClusterHandle, TpuGangBackend
        handle = ClusterHandle.from_dict(record['handle'])
        backend = TpuGangBackend()
        if backend.is_remote_controlled(handle):
            try:
                head_jobs = backend.job_queue(handle)
            except Exception:  # noqa: BLE001 — no data => no action
                return None
            if any(not job_lib.JobStatus(j['status']).is_terminal()
                   for j in head_jobs):
                return None
            jobs = head_jobs[:1]
    if jobs is None:
        table = job_lib.JobTable(_runtime_dir(cluster_name))
        if table.unfinished_jobs():
            return None
        jobs = table.list_jobs(limit=1)
    candidates = []
    if jobs and jobs[0].get('ended_at'):
        candidates.append(jobs[0]['ended_at'])
    if record is not None and record.get('last_activity'):
        candidates.append(record['last_activity'])
    if not candidates:
        return None
    return time.time() - max(candidates)


def check_once(cluster_name: str) -> Optional[str]:
    """Evaluate the autostop policy once. Returns 'stop'/'down' if it acted,
    None otherwise."""
    path = os.path.join(_runtime_dir(cluster_name), constants.AUTOSTOP_FILE)
    try:
        with open(path, encoding='utf-8') as f:
            policy = json.load(f)
    except (FileNotFoundError, json.JSONDecodeError):
        return None
    idle_minutes = policy.get('idle_minutes', -1)
    if idle_minutes is None or idle_minutes < 0:
        return None
    idle = _idle_seconds(cluster_name)
    if idle is None or idle < idle_minutes * 60:
        return None
    from skypilot_tpu import core
    try:
        if policy.get('down'):
            core.down(cluster_name)
            blackbox.record('agent.autostop', action='down',
                            cluster=cluster_name)
            return 'down'
        core.stop(cluster_name)
        blackbox.record('agent.autostop', action='stop',
                        cluster=cluster_name)
        return 'stop'
    except exceptions.NotSupportedError:
        # Cloud cannot stop (e.g. local): fall back to down.
        core.down(cluster_name)
        blackbox.record('agent.autostop', action='down',
                        cluster=cluster_name)
        return 'down'
    except exceptions.ClusterDoesNotExist:
        return None


def heartbeat_once(cluster_name: str,
                   interval_s: float = 20.0) -> Optional[dict]:
    """Assemble and store one heartbeat. Best-effort throughout: a
    heartbeat failure must never take the autostop daemon down, so every
    probe degrades to omission. Returns the stored payload (tests), or
    None when the cluster row is gone."""
    payload: dict = {'ts': time.time(), 'interval_s': interval_s}
    try:
        import shutil
        cdir = _runtime_dir(cluster_name)
        usage = shutil.disk_usage(
            cdir if os.path.isdir(cdir) else os.path.expanduser('~'))
        payload['host'] = {
            'disk_free_gb': round(usage.free / 1e9, 2),
            'disk_used_pct': round(100.0 * usage.used / max(usage.total, 1),
                                   1),
        }
    except OSError:
        pass
    try:
        # Same /proc probe tpu_doctor's process table uses — a leaked
        # framework daemon on this host shows up in the heartbeat long
        # before it wedges the device tunnel.
        from skypilot_tpu.utils import tpu_doctor
        payload.setdefault('host', {})['framework_procs'] = len(
            tpu_doctor.framework_processes())
    except Exception:  # noqa: BLE001 — /proc probing is best-effort
        pass
    try:
        table = job_lib.JobTable(_runtime_dir(cluster_name))
        unfinished = table.unfinished_jobs()
        latest = table.list_jobs(limit=1)
        payload['jobs'] = {'unfinished': len(unfinished)}
        if latest:
            payload['jobs']['latest'] = {
                'job_id': latest[0]['job_id'],
                'status': latest[0]['status'],
            }
    except Exception:  # noqa: BLE001 — job table may not exist yet
        pass
    try:
        # One pass over the spools yields both the newest training
        # window and the cumulative checkpoint accounting (the latter
        # surfaces as skytpu_ckpt_* gauges at metrics scrape time).
        from skypilot_tpu.observability import train_telemetry
        summary = train_telemetry.cluster_telemetry_summary(
            _runtime_dir(cluster_name))
        if summary['train'] is not None:
            payload['train'] = summary['train']
        if summary['ckpt'] is not None:
            payload['ckpt'] = summary['ckpt']
    except Exception:  # noqa: BLE001 — telemetry spool is optional
        pass
    try:
        if not global_user_state.record_heartbeat(cluster_name, payload):
            return None
    except Exception:  # noqa: BLE001 — a full disk / corrupt DB must not
        return None  # kill the autostop daemon; next tick retries
    blackbox.record('agent.heartbeat', cluster=cluster_name,
                    unfinished=(payload.get('jobs') or {}).get(
                        'unfinished'))
    return payload


def run_loop(cluster_name: str, interval_s: float = 20.0) -> None:
    """Daemon loop (20 s tick, matching the reference's SkyletEvent)."""
    while True:
        record = global_user_state.get_cluster(cluster_name)
        if record is None:
            return  # cluster downed: daemon exits
        heartbeat_once(cluster_name, interval_s)
        acted = check_once(cluster_name)
        if acted == 'down':
            return
        time.sleep(interval_s)


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument('--cluster-name', required=True)
    parser.add_argument('--interval', type=float, default=20.0)
    args = parser.parse_args()
    # kill -QUIT interrogates a wedged daemon without killing it:
    # faulthandler stacks land in the bundle spool, not stderr.
    blackbox.set_process_label('agent_daemon')
    blackbox.install_sigquit()
    run_loop(args.cluster_name, args.interval)


if __name__ == '__main__':
    main()
