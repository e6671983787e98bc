"""Web dashboard: fleet state in the browser, drillable per entity.

Reference analog: ``sky/dashboard/`` (a 29k-LoC Next.js app served from
the API server, ``server.py:2100``). TPU-native build keeps the dashboard
dependency-free: one self-contained HTML page (no build step, no node)
with hash-routed views — overview, per-cluster detail with live job log
tail, per-managed-job detail, per-service detail with a replica/throughput
chart, users and workspaces admin views — all over read-only JSON
endpoints.

Routes (registered by ``server.py``):
  GET /dashboard                           -> the app
  GET /dashboard/api/state                 -> overview snapshot
  GET /dashboard/api/cluster/{name}        -> cluster detail (+events,+jobs)
  GET /dashboard/api/cluster/{name}/logs   -> job log tail (?job_id=, ?lines=)
  GET /dashboard/api/job/{job_id}          -> managed-job detail
  GET /dashboard/api/service/{name}        -> service detail (+replicas)
  GET /dashboard/api/users                 -> users + roles
  GET /dashboard/api/workspaces            -> workspaces + membership counts
  GET /dashboard/api/metrics/history       -> fleet time-series ring buffer
  GET /dashboard/api/infra                 -> clouds/catalogs/server health
  GET /dashboard/api/config                -> layered config (redacted)
  GET /dashboard/api/fleet                 -> heartbeats + job goodput
  GET /dashboard/api/incidents             -> incident-bundle spool list
  GET /dashboard/api/incident/{file}       -> one full incident bundle
  GET /dashboard/api/remediation           -> self-healing decision log
"""
from __future__ import annotations

import asyncio
import os
from typing import Any, Dict, List, Optional

from aiohttp import web


def state_snapshot() -> Dict[str, Any]:
    """Synchronous read-only snapshot of all state tables (cheap SQLite
    reads — no request-executor round trip needed for a dashboard poll)."""
    from skypilot_tpu import global_user_state
    from skypilot_tpu.jobs import state as jobs_state
    from skypilot_tpu.serve import serve_state
    from skypilot_tpu.server import requests_db

    clusters = []
    for rec in global_user_state.get_clusters():
        handle = rec.get('handle') or {}
        res = handle.get('launched_resources') or {}
        clusters.append({
            'name': rec['name'],
            'status': rec['status'].value,
            'cloud': handle.get('cloud'),
            'region': handle.get('region'),
            'resources': res.get('accelerators') or res.get('instance_type')
            or res.get('cpus') or '-',
            'nodes': handle.get('num_nodes'),
            'price_per_hour': handle.get('price_per_hour'),
            'launched_at': rec.get('launched_at'),
            'workspace': rec.get('workspace'),
        })
    jobs = [{
        'job_id': r['job_id'],
        'name': r['name'],
        'status': r['status'].value,
        'schedule_state': r.get('schedule_state'),
        'cluster': r['cluster_name'],
        'recoveries': r['recovery_count'],
        'submitted_at': r['submitted_at'],
    } for r in jobs_state.list_jobs()]
    services = []
    for svc in serve_state.list_services():
        if svc is None:
            continue
        replicas = serve_state.list_replicas(svc['name'])
        services.append({
            'name': svc['name'],
            'status': svc['status'].value,
            'endpoint': svc['endpoint'],
            'version': svc.get('version'),
            'replicas': [{
                'replica_id': r['replica_id'],
                'status': r['status'].value,
                'version': r.get('version'),
                'endpoint': r['endpoint'],
                'use_spot': bool(r.get('use_spot')),
                'weight': r.get('weight'),
            } for r in replicas],
        })
    return {
        'clusters': clusters,
        'jobs': jobs,
        'services': services,
        'requests': requests_db.list_requests(limit=50),
    }


def _cluster_jobs(name: str) -> List[Dict[str, Any]]:
    """The cluster's on-head job queue; remote heads are asked through the
    agent (short timeout), unreachable heads return []."""
    from skypilot_tpu import global_user_state
    from skypilot_tpu.backends import ClusterHandle, TpuGangBackend
    rec = global_user_state.get_cluster(name)
    if not rec or not rec.get('handle'):
        return []
    if rec['status'] != global_user_state.ClusterStatus.UP:
        return []  # stopped/init head has no queue to ask
    try:
        backend = TpuGangBackend()
        handle = ClusterHandle.from_dict(rec['handle'])
        return backend.job_queue(handle)[:50]
    except Exception:  # noqa: BLE001 — dashboard read must not 500
        return []


def cluster_detail(name: str) -> Optional[Dict[str, Any]]:
    from skypilot_tpu import global_user_state
    rec = global_user_state.get_cluster(name)
    if rec is None:
        return None
    handle = rec.get('handle') or {}
    return {
        'name': name,
        'status': rec['status'].value,
        'workspace': rec.get('workspace'),
        'owner': rec.get('owner'),
        'launched_at': rec.get('launched_at'),
        'autostop_minutes': rec.get('autostop_minutes'),
        'handle': handle,
        'events': global_user_state.get_cluster_events(name, limit=50),
        'jobs': _cluster_jobs(name),
    }


def _job_log_tail(cluster: str, job_id: Optional[int],
                  lines: int = 500) -> Dict[str, Any]:
    """Last N log lines of a job (newest job when unspecified): local
    clusters read the runtime dir; remote-control clusters ask the head
    agent."""
    from skypilot_tpu import global_user_state
    from skypilot_tpu.backends import ClusterHandle, TpuGangBackend
    from skypilot_tpu.backends.tpu_gang_backend import runtime_dir
    rec = global_user_state.get_cluster(cluster)
    if not rec or not rec.get('handle'):
        return {'error': 'cluster not found', 'lines': []}
    handle = ClusterHandle.from_dict(rec['handle'])
    backend = TpuGangBackend()
    try:
        if backend.is_remote_controlled(handle):
            from skypilot_tpu.agent import remote as remote_lib
            client = remote_lib.agent_client(
                cluster, backend._head_spec(handle))  # pylint: disable=protected-access
            if job_id is None:
                jobs = client.list_jobs(limit=1)
                if not jobs:
                    return {'job_id': None, 'lines': []}
                job_id = jobs[0]['job_id']
            out = ''.join(client.tail_log(job_id, lines=lines,
                                          follow=False))
            return {'job_id': job_id, 'lines': out.splitlines()[-lines:]}
        cdir = runtime_dir(cluster)
        if job_id is None:
            from skypilot_tpu.agent import job_lib
            jobs = job_lib.JobTable(cdir).list_jobs(limit=1)
            if not jobs:
                return {'job_id': None, 'lines': []}
            job_id = jobs[0]['job_id']
        path = os.path.join(cdir, 'jobs', str(job_id), 'run.log')
        if not os.path.exists(path):
            return {'job_id': job_id, 'lines': []}
        with open(path, 'rb') as f:
            data = f.read()[-1 << 20:]
        return {'job_id': job_id,
                'lines': data.decode('utf-8',
                                     errors='replace').splitlines()[-lines:]}
    except Exception as e:  # noqa: BLE001 — dashboard read must not 500
        return {'job_id': job_id, 'lines': [], 'error': str(e)}


def fleet_view() -> Dict[str, Any]:
    """The fleet telemetry panel: per-cluster heartbeat health (age,
    staleness, disk, newest training window) + per-job goodput from the
    phase ledger. Pure state-table reads — the ledger aggregation is ONE
    grouped query (``phase_totals``), not a per-job fan-out, so the 2 s
    dashboard poll stays cheap at fleet scale."""
    from skypilot_tpu import global_user_state
    from skypilot_tpu.jobs import state as jobs_state

    clusters = []
    for rec in global_user_state.get_clusters():
        hb = rec.get('heartbeat') or {}
        age, stale = global_user_state.heartbeat_age(rec)
        clusters.append({
            'name': rec['name'],
            'status': rec['status'].value,
            'heartbeat_age': round(age, 1) if age is not None else None,
            'heartbeat_stale': stale,
            'host': hb.get('host'),
            'jobs': hb.get('jobs'),
            'train': hb.get('train'),
        })
    totals = jobs_state.phase_totals()
    jobs = []
    for rec in jobs_state.list_jobs(limit=100):
        phases = totals.get(rec['job_id'])
        if not phases:
            continue  # predates the ledger
        wall = sum(phases.values())
        jobs.append({
            'job_id': rec['job_id'],
            'name': rec['name'],
            'cluster': rec['cluster_name'],
            'status': rec['status'].value,
            'wall_s': round(wall, 3),
            'phases': {k: round(v, 3) for k, v in sorted(phases.items())},
            'goodput_ratio': round(phases.get('running', 0.0) / wall, 4)
                             if wall > 0 else 0.0,
            'recoveries': rec['recovery_count'],
        })
    return {'clusters': clusters, 'jobs': jobs}


def job_detail(job_id: int) -> Optional[Dict[str, Any]]:
    from skypilot_tpu.jobs import state as jobs_state
    rec = jobs_state.get(job_id)
    if rec is None:
        return None
    return {
        'goodput': jobs_state.goodput_summary(job_id),
        'ledger': jobs_state.phase_ledger(job_id),
        'job_id': job_id,
        'name': rec['name'],
        'status': rec['status'].value,
        'schedule_state': rec.get('schedule_state'),
        'cluster': rec['cluster_name'],
        'recoveries': rec['recovery_count'],
        'controller_pid': rec.get('controller_pid'),
        'controller_restarts': rec.get('controller_restarts'),
        'recovery_strategy': rec.get('recovery_strategy'),
        'submitted_at': rec.get('submitted_at'),
        'detail': rec.get('detail'),
        'task_config': rec.get('task_config'),
    }


def service_detail(name: str) -> Optional[Dict[str, Any]]:
    from skypilot_tpu.serve import serve_state
    svc = serve_state.get_service(name)
    if svc is None:
        return None
    return {
        'name': name,
        'status': svc['status'].value,
        'endpoint': svc['endpoint'],
        'version': svc.get('version'),
        'controller_pid': svc.get('controller_pid'),
        'controller_restarts': svc.get('controller_restarts'),
        'spec': svc.get('spec'),
        'replicas': [{
            'replica_id': r['replica_id'],
            'status': r['status'].value,
            'version': r.get('version'),
            'endpoint': r['endpoint'],
            'cluster_name': r.get('cluster_name'),
            'use_spot': bool(r.get('use_spot')),
            'weight': r.get('weight'),
            'created_at': r.get('created_at'),
            'health': serve_state.parse_health(r.get('health')),
        } for r in serve_state.list_replicas(name)],
    }


def logs_search_view(query: str, max_matches: int = 300,
                     tail_bytes: int = 2 * 1024 * 1024) -> Dict[str, Any]:
    """Case-insensitive substring search across every cluster job log
    (reference analog: the dashboard's log search). Bounded: only the
    last ``tail_bytes`` of each file are scanned and matches cap at
    ``max_matches`` — a dashboard query must stay cheap no matter how
    much log history exists."""
    import glob

    from skypilot_tpu.backends.tpu_gang_backend import runtime_dir
    q = query.lower()
    if not q:
        return {'matches': [], 'truncated': False, 'files_scanned': 0}
    root = os.path.dirname(runtime_dir('x'))  # .../runtime
    matches: List[Dict[str, Any]] = []
    truncated = False
    scanned = 0
    def _mtime_or_zero(path: str) -> float:
        try:  # a teardown may delete the file between glob and sort
            return os.path.getmtime(path)
        except OSError:
            return 0.0

    files = sorted(glob.glob(os.path.join(root, '*', 'jobs', '*', '*.log')),
                   key=_mtime_or_zero, reverse=True)
    for path in files:
        rel = os.path.relpath(path, root)
        parts = rel.split(os.sep)  # cluster/jobs/<id>/<file>.log
        cluster, job_id, fname = parts[0], parts[2], parts[3]
        try:
            size = os.path.getsize(path)
            with open(path, 'rb') as f:
                if size > tail_bytes:
                    f.seek(size - tail_bytes)
                    f.readline()  # drop the partial line
                text = f.read().decode('utf-8', errors='replace')
        except OSError:
            continue
        scanned += 1
        for i, line in enumerate(text.splitlines(), start=1):
            if q in line.lower():
                matches.append({'cluster': cluster, 'job_id': job_id,
                                'file': fname, 'line_no': i,
                                'line': line[:400]})
                if len(matches) >= max_matches:
                    truncated = True
                    break
        if truncated:
            break
    # files_scanned counts files actually OPENED: an early break must
    # not claim coverage of files the search never reached.
    return {'matches': matches, 'truncated': truncated,
            'files_scanned': scanned}


_SERVER_STARTED_AT = __import__('time').time()


def metrics_history_view() -> Dict[str, Any]:
    """The sampler's ring buffer + a fresh (unrecorded) sample so charts
    always have a current point. The GET must not append on every poll:
    the dashboard refreshes every 2s and would evict the 4h@15s window
    the daemon maintains — the view only records when the buffer has no
    recent sample (daemon disabled or not yet ticked)."""
    import time as time_lib

    from skypilot_tpu.server import metrics_history
    hist = metrics_history.history()
    interval = metrics_history.sample_interval_s()
    # Record only as the FALLBACK sampler (daemon disabled, or clearly
    # dead — 2x its interval without a tick; a bare >= interval would
    # race the daemon's sleep+work cadence and double the density).
    stale = (not hist or interval <= 0 or
             time_lib.time() - hist[-1]['ts'] >= max(2 * interval, 2.0))
    fresh = metrics_history.sample_once(record=stale)
    samples = metrics_history.history() if stale else hist + [fresh]
    return {'samples': samples, 'sample_interval_s': interval}


def infra_view() -> Dict[str, Any]:
    """Infra/admin page data: clouds enabled, catalog freshness, API
    server health (reference analog: the dashboard's infra pages)."""
    import glob
    import sys
    import time as time_lib

    from skypilot_tpu import check as check_lib
    from skypilot_tpu.catalog import common as catalog_common
    from skypilot_tpu.server import requests_db

    clouds = [{'name': name, 'enabled': ok, 'reason': reason}
              for name, (ok, reason) in sorted(
                  check_lib.check_capabilities(quiet=True).items())]

    catalogs = []
    data_dir = catalog_common._PACKAGE_DATA_DIR  # noqa: SLF001
    for path in sorted(glob.glob(os.path.join(data_dir, '**', '*.csv'),
                                 recursive=True)):
        try:
            with open(path, encoding='utf-8') as f:
                rows = sum(1 for _ in f) - 1
            catalogs.append({
                'file': os.path.relpath(path, data_dir),
                'rows': rows,
                'age_days': round(
                    (time_lib.time() - os.path.getmtime(path)) / 86400, 1),
            })
        except OSError:
            continue

    import importlib.metadata as importlib_metadata
    try:
        # Version from package metadata: importing jax into the
        # control-plane process costs seconds + backend init.
        jax_version = importlib_metadata.version('jax')
    except importlib_metadata.PackageNotFoundError:
        jax_version = None
    return {
        'clouds': clouds,
        'catalogs': catalogs,
        'server': {
            'pid': os.getpid(),
            'uptime_s': round(time_lib.time() - _SERVER_STARTED_AT, 1),
            'python': sys.version.split()[0],
            'jax': jax_version,
            'active_requests_long': requests_db.count_active('long'),
            'active_requests_short': requests_db.count_active('short'),
            'state_dir': os.environ.get('SKYTPU_STATE_DIR',
                                        '~/.skypilot_tpu'),
            'db_backend': ('postgres'
                           if os.environ.get('SKYTPU_DB_URL') else 'sqlite'),
        },
    }


_SECRET_KEY_HINTS = ('token', 'secret', 'password', 'key', 'credential')


def _redact(obj: Any) -> Any:
    if isinstance(obj, dict):
        return {k: ('***' if any(h in str(k).lower()
                                 for h in _SECRET_KEY_HINTS)
                    else _redact(v)) for k, v in obj.items()}
    if isinstance(obj, list):
        return [_redact(v) for v in obj]
    return obj


def config_view() -> Dict[str, Any]:
    """The layered config as the server resolves it, secrets redacted."""
    from skypilot_tpu import config as config_lib
    return {
        'config': _redact(config_lib.to_dict()),
        'loaded_from': config_lib.loaded_config_path(),
    }


def users_view() -> List[Dict[str, Any]]:
    from skypilot_tpu import users as users_lib
    try:
        return [{'name': u['name'], 'role': u['role'],
                 'created_at': u.get('created_at')}
                for u in users_lib.list_users()]
    except Exception:  # noqa: BLE001 — no users table yet
        return []


def workspaces_view() -> List[Dict[str, Any]]:
    from skypilot_tpu import global_user_state
    from skypilot_tpu import workspaces as workspaces_lib
    clusters = global_user_state.get_clusters()
    out = []
    for ws in workspaces_lib.list_workspaces():
        n = sum(1 for c in clusters if c.get('workspace') == ws['name'])
        out.append({'name': ws['name'], 'created_at': ws.get('created_at'),
                    'created_by': ws.get('created_by'), 'clusters': n})
    return out


# -- aiohttp handlers --------------------------------------------------------
# Blocking reads run in a DEDICATED small pool with a hard deadline: an
# unreachable remote head (dead tunnel, stopped VM) must not pile up
# 2-second dashboard polls until every executor thread is stuck and all
# endpoints stall for every viewer. On deadline the poll degrades to 504;
# the stuck thread finishes (or times out) in the background.

import concurrent.futures as _cf

_POOL = _cf.ThreadPoolExecutor(max_workers=4,
                               thread_name_prefix='dashboard')
_READ_DEADLINE_S = 5.0


async def _json(request: web.Request, fn, *args) -> web.Response:
    loop = asyncio.get_event_loop()
    try:
        result = await asyncio.wait_for(
            loop.run_in_executor(_POOL, fn, *args),
            timeout=_READ_DEADLINE_S)
    except asyncio.TimeoutError:
        return web.json_response(
            {'error': 'state read timed out (cluster head unreachable?)'},
            status=504)
    if result is None:
        return web.json_response({'error': 'not found'}, status=404)
    return web.json_response(result)


async def api_state(request: web.Request) -> web.Response:
    return await _json(request, state_snapshot)


async def api_cluster(request: web.Request) -> web.Response:
    return await _json(request, cluster_detail,
                       request.match_info['name'])


def _int_or(value, default):
    try:
        return int(value)
    except (TypeError, ValueError):
        return default


async def api_cluster_logs(request: web.Request) -> web.Response:
    job_id = _int_or(request.query.get('job_id'), None)
    lines = min(max(_int_or(request.query.get('lines'), 500), 1), 10000)
    return await _json(request, _job_log_tail, request.match_info['name'],
                       job_id, lines)


async def api_job(request: web.Request) -> web.Response:
    job_id = _int_or(request.match_info['job_id'], None)
    if job_id is None:
        return web.json_response({'error': 'bad job id'}, status=400)
    return await _json(request, job_detail, job_id)


async def api_service(request: web.Request) -> web.Response:
    return await _json(request, service_detail,
                       request.match_info['name'])


async def api_users(request: web.Request) -> web.Response:
    return await _json(request, users_view)


async def api_workspaces(request: web.Request) -> web.Response:
    return await _json(request, workspaces_view)


async def api_metrics_history(request: web.Request) -> web.Response:
    return await _json(request, metrics_history_view)


async def api_logs_search(request: web.Request) -> web.Response:
    q = request.query.get('q', '')
    limit = min(max(_int_or(request.query.get('limit'), 300), 1), 2000)
    return await _json(request, logs_search_view, q, limit)


def alerts_view() -> Dict[str, Any]:
    """The #/alerts panel's data: the SLO engine's active alerts plus
    resolved history and the rule catalog (observability/slo.py). The
    metrics view also polls this to overlay firing intervals on the
    charts."""
    from skypilot_tpu.observability import slo
    return slo.alerts_payload({'history': '1', 'rules': '1'})


async def api_alerts(request: web.Request) -> web.Response:
    return await _json(request, alerts_view)


def incidents_view() -> Dict[str, Any]:
    """The incident panel's data: the API-server host's bundle spool
    (observability/blackbox.py), newest first. Replica-local bundles
    are fetched from the replicas' own /debug/blackbox or via
    `stpu debug dump <cluster>` — the panel documents that."""
    from skypilot_tpu.observability import blackbox
    return {'dir': blackbox.spool_dir(), 'enabled': blackbox.enabled(),
            'bundles': blackbox.list_bundles(limit=50)}


def incident_detail(fname: str) -> Optional[Dict[str, Any]]:
    from skypilot_tpu.observability import blackbox
    return blackbox.read_bundle(fname)


def remediation_view() -> Dict[str, Any]:
    """The #/remediation panel's data: the self-healing engine's
    journaled decisions (serve/remediation.py). The controller
    persists each service's record log atomically under
    $SKYTPU_STATE_DIR, so this read works from the API-server process
    even for detached controllers; the live payload (budget tokens,
    placer state) stays at the LB's /debug/remediations."""
    import dataclasses
    import glob
    import json

    from skypilot_tpu.serve import remediation as remediation_lib
    state_dir = os.path.expanduser(
        os.environ.get('SKYTPU_STATE_DIR', '~/.skypilot_tpu'))
    records: List[Dict[str, Any]] = []
    for path in sorted(glob.glob(
            os.path.join(state_dir, 'remediations-*.json'))):
        try:
            with open(path, encoding='utf-8') as f:
                data = json.load(f)
        except (OSError, ValueError):
            continue
        if isinstance(data, dict):
            records.extend(r for r in (data.get('records') or [])
                           if isinstance(r, dict))
    records.sort(key=lambda r: r.get('ts') or 0, reverse=True)
    return {'mode': remediation_lib.mode(),
            'actions': [dataclasses.asdict(a)
                        for a in remediation_lib.ACTIONS],
            'records': records[:200]}


async def api_remediation(request: web.Request) -> web.Response:
    return await _json(request, remediation_view)


async def api_incidents(request: web.Request) -> web.Response:
    return await _json(request, incidents_view)


async def api_incident(request: web.Request) -> web.Response:
    return await _json(request, incident_detail,
                       request.match_info['file'])


async def api_infra(request: web.Request) -> web.Response:
    return await _json(request, infra_view)


async def api_fleet(request: web.Request) -> web.Response:
    return await _json(request, fleet_view)


async def api_config(request: web.Request) -> web.Response:
    return await _json(request, config_view)


def add_routes(app: web.Application) -> None:
    app.router.add_get('/dashboard', page)
    app.router.add_get('/dashboard/api/state', api_state)
    app.router.add_get('/dashboard/api/cluster/{name}', api_cluster)
    app.router.add_get('/dashboard/api/cluster/{name}/logs',
                       api_cluster_logs)
    app.router.add_get('/dashboard/api/job/{job_id}', api_job)
    app.router.add_get('/dashboard/api/service/{name}', api_service)
    app.router.add_get('/dashboard/api/users', api_users)
    app.router.add_get('/dashboard/api/workspaces', api_workspaces)
    app.router.add_get('/dashboard/api/metrics/history',
                       api_metrics_history)
    app.router.add_get('/dashboard/api/logs/search', api_logs_search)
    app.router.add_get('/dashboard/api/infra', api_infra)
    app.router.add_get('/dashboard/api/config', api_config)
    app.router.add_get('/dashboard/api/fleet', api_fleet)
    app.router.add_get('/dashboard/api/incidents', api_incidents)
    app.router.add_get('/dashboard/api/incident/{file}', api_incident)
    app.router.add_get('/dashboard/api/alerts', api_alerts)
    app.router.add_get('/dashboard/api/remediation', api_remediation)


_PAGE = """<!doctype html>
<html><head><meta charset="utf-8"><title>skypilot-tpu</title>
<style>
 body{font-family:system-ui,sans-serif;margin:24px;background:#fafafa;
      color:#1a1a1a}
 h1{font-size:20px} h2{font-size:15px;margin:24px 0 8px}
 a{color:#0b57d0;text-decoration:none} a:hover{text-decoration:underline}
 nav a{margin-right:14px;font-size:13px}
 table{border-collapse:collapse;width:100%;background:#fff;
       box-shadow:0 1px 2px rgba(0,0,0,.08)}
 th,td{padding:6px 10px;text-align:left;font-size:13px;
       border-bottom:1px solid #eee}
 th{background:#f0f0f3;font-weight:600}
 .b{display:inline-block;padding:1px 8px;border-radius:9px;font-size:12px}
 .UP,.RUNNING,.READY,.SUCCEEDED,.ALIVE{background:#d9f2e2;color:#066a2e}
 .INIT,.PENDING,.STARTING,.PROVISIONING,.SUBMITTED,.RECOVERING,.WAITING,
 .LAUNCHING,.SETTING_UP,.REPLICA_INIT,.CONTROLLER_INIT{background:#fdf2d0;
 color:#7a5b00}
 .STOPPED,.CANCELLED,.SHUTDOWN,.DONE{background:#e8e8ec;color:#444}
 .FAILED,.FAILED_SETUP,.FAILED_CONTROLLER,.FAILED_NO_RESOURCE,.NOT_READY
 {background:#fbdcd9;color:#9d1c0e}
 .page,.firing{background:#fbdcd9;color:#9d1c0e}
 .warn,.pending{background:#fdf2d0;color:#7a5b00}
 .info{background:#e0ecff;color:#0b57d0}
 .resolved{background:#e8e8ec;color:#444}
 #ts{color:#888;font-size:12px}
 pre.log{background:#101418;color:#d7e2ea;padding:12px;border-radius:6px;
      font-size:12px;max-height:420px;overflow:auto;white-space:pre-wrap}
 .kv td:first-child{color:#666;width:220px}
 svg.chart{background:#fff;box-shadow:0 1px 2px rgba(0,0,0,.08);
      border-radius:4px}
</style></head><body>
<h1>skypilot-tpu <span id="ts"></span></h1>
<nav><a href="#/">overview</a> <a href="#/metrics">metrics</a>
 <a href="#/alerts">alerts</a> <a href="#/remediation">remediation</a>
 <a href="#/traces">traces</a> <a href="#/incidents">incidents</a>
 <a href="#/fleet">fleet</a>
 <a href="#/logs">logs</a> <a href="#/infra">infra</a>
 <a href="#/config">config</a> <a href="#/users">users</a>
 <a href="#/workspaces">workspaces</a></nav>
<div id="view"></div>
<script>
// Token-protected servers: open /dashboard?token=...; the token rides
// along on every api poll.
const TOKEN = new URLSearchParams(location.search).get('token');
const HDRS = TOKEN ? {'Authorization': 'Bearer ' + TOKEN} : {};
// Escape EVERYTHING interpolated into innerHTML: names/endpoints/logs are
// user-controlled (stored-XSS vector otherwise).
const esc = v => String(v ?? '-').replace(/[&<>"']/g,
    ch => ({'&':'&amp;','<':'&lt;','>':'&gt;','"':'&quot;',"'":'&#39;'}[ch]));
const B = s => `<span class="b ${esc(s)}">${esc(s)}</span>`;
const T = t => t ? new Date(t*1000).toLocaleTimeString() : '-';
const J = async p => {
  const r = await fetch(p, {headers: HDRS});
  if(!r.ok) throw new Error(p + ' -> ' + r.status);
  return r.json();
};
const table = (cols, rows, render) =>
  '<table><tr>' + cols.map(c=>`<th>${c}</th>`).join('') + '</tr>' +
  (rows.length ? rows.map(render).join('')
               : `<tr><td colspan="${cols.length}">none</td></tr>`) +
  '</table>';
const kv = obj => '<table class="kv">' + Object.entries(obj).map(
  ([k,v])=>`<tr><td>${esc(k)}</td><td>${v}</td></tr>`).join('') + '</table>';

// Per-service time series the chart view accumulates while open:
// [t, readyReplicas, reqPerPoll].
const series = {};
function sparkline(data, color, ymax){
  if(data.length < 2) return '(collecting…)';
  const W=560, H=80, n=data.length;
  const pts = data.map((v,i)=>
    `${(i/(n-1)*W).toFixed(1)},${(H-4-(v/Math.max(ymax,1))*(H-8)).toFixed(1)}`);
  return `<svg class="chart" width="${W}" height="${H}">`+
    `<polyline fill="none" stroke="${color}" stroke-width="2" `+
    `points="${pts.join(' ')}"/></svg>`;
}

async function overview(){
  const s = await J('dashboard/api/state');
  return `<h2>Clusters</h2>` + table(
    ['name','status','cloud','region','resources','nodes','$/hr','ws',
     'launched'], s.clusters,
    c=>`<tr><td><a href="#/cluster/${esc(c.name)}">${esc(c.name)}</a></td>
     <td>${B(c.status)}</td><td>${esc(c.cloud)}</td><td>${esc(c.region)}</td>
     <td>${esc(c.resources)}</td><td>${c.nodes??'-'}</td>
     <td>${c.price_per_hour!=null?c.price_per_hour.toFixed(2):'-'}</td>
     <td>${esc(c.workspace)}</td><td>${T(c.launched_at)}</td></tr>`) +
  `<h2>Managed jobs</h2>` + table(
    ['id','name','status','schedule','cluster','recoveries','submitted'],
    s.jobs,
    j=>`<tr><td><a href="#/job/${j.job_id}">${esc(j.job_id)}</a></td>
     <td>${esc(j.name)}</td><td>${B(j.status)}</td>
     <td>${B(j.schedule_state)}</td>
     <td><a href="#/cluster/${esc(j.cluster)}">${esc(j.cluster)}</a></td>
     <td>${esc(j.recoveries)}</td><td>${T(j.submitted_at)}</td></tr>`) +
  `<h2>Services</h2>` + table(
    ['name','status','version','endpoint','replicas'], s.services,
    v=>`<tr><td><a href="#/service/${esc(v.name)}">${esc(v.name)}</a></td>
     <td>${B(v.status)}</td><td>v${v.version??1}</td>
     <td>${esc(v.endpoint)}</td>
     <td>${v.replicas.map(r=>`#${esc(r.replica_id)} ${B(r.status)}`)
          .join(' ')}</td></tr>`) +
  `<h2>API requests</h2>` + table(
    ['request id','op','status','created','finished'], s.requests,
    r=>`<tr><td>${esc(r.request_id)}</td><td>${esc(r.name)}</td>
     <td>${B(r.status)}</td><td>${T(r.created_at)}</td>
     <td>${T(r.finished_at)}</td></tr>`);
}

async function clusterView(name){
  const c = await J('dashboard/api/cluster/' + encodeURIComponent(name));
  const logs = await J('dashboard/api/cluster/' +
                       encodeURIComponent(name) + '/logs');
  const h = c.handle || {};
  return `<h2>Cluster ${esc(name)}</h2>` + kv({
      status: B(c.status), cloud: esc(h.cloud), region: esc(h.region),
      zone: esc(h.zone), nodes: esc(h.num_nodes),
      'hosts/node': esc(h.hosts_per_node),
      'chips/host': esc(h.chips_per_host),
      workspace: esc(c.workspace), owner: esc(c.owner),
      'autostop (min)': esc(c.autostop_minutes),
      '$/hr': h.price_per_hour!=null?h.price_per_hour.toFixed(2):'-',
      launched: T(c.launched_at)}) +
    `<h2>Job queue</h2>` + table(
      ['id','name','status','submitted','ended'], c.jobs||[],
      j=>`<tr><td>${esc(j.job_id)}</td><td>${esc(j.name)}</td>
       <td>${B(j.status)}</td><td>${T(j.submitted_at)}</td>
       <td>${T(j.ended_at)}</td></tr>`) +
    `<h2>Log tail ${logs.job_id!=null?'(job '+esc(logs.job_id)+')':''}</h2>`+
    `<pre class="log">${esc((logs.lines||[]).join('\\n')) || '(no logs)'}`+
    `</pre>` +
    `<h2>Events</h2>` + table(
      ['time','event','detail'], c.events||[],
      e=>`<tr><td>${T(e.timestamp)}</td><td>${esc(e.event)}</td>
       <td>${esc(e.detail)}</td></tr>`);
}

// Stacked wall-clock bar from a goodput summary's {phase: seconds}.
const PHASE_COLOR = {running:'#0a7d33', recovering:'#b3261e',
  launching:'#7a5b00', pending:'#a0a0a8', cancelling:'#52525b'};
function goodputBar(g){
  if(!g || !g.wall_s) return '';
  const segs = Object.entries(g.phases).map(([p,s])=>
    `<div title="${esc(p)} ${s.toFixed(1)}s" style="display:inline-block;
      height:14px;width:${(100*s/g.wall_s).toFixed(2)}%;
      background:${PHASE_COLOR[p]||'#888'}"></div>`).join('');
  return `<div style="width:100%;background:#f0f0f3;border-radius:3px;
    overflow:hidden;white-space:nowrap">${segs}</div>`;
}
const goodputLegend = Object.entries(PHASE_COLOR).map(([p,c])=>
  `<span style="color:${c};font-size:11px;margin-right:8px">&#9632; ${p}
   </span>`).join('');

async function jobView(id){
  const j = await J('dashboard/api/job/' + id);
  const g = j.goodput;
  const goodputHtml = g ? `<h2>Goodput ${
      (100*g.goodput_ratio).toFixed(1)}% of ${g.wall_s.toFixed(1)}s
      wall-clock</h2>` + goodputBar(g) + `<div>${goodputLegend}</div>` +
    table(['phase','kind','start','seconds','detail'], j.ledger||[],
      r=>`<tr><td>${esc(r.phase)}</td><td>${esc(r.kind)}</td>
       <td>${T(r.started_at)}</td>
       <td>${r.ended_at!=null?(r.ended_at-r.started_at).toFixed(2):'(open)'}
       </td><td>${esc(r.detail)}</td></tr>`) : '';
  return `<h2>Managed job ${esc(id)}: ${esc(j.name)}</h2>` + kv({
      status: B(j.status), schedule: B(j.schedule_state),
      cluster: `<a href="#/cluster/${esc(j.cluster)}">${esc(j.cluster)}</a>`,
      recoveries: esc(j.recoveries),
      'recovery strategy': esc(j.recovery_strategy),
      'controller pid': esc(j.controller_pid),
      'controller restarts': esc(j.controller_restarts),
      submitted: T(j.submitted_at), detail: esc(j.detail)}) +
    goodputHtml +
    `<h2>Task config</h2><pre class="log">${
      esc(JSON.stringify(j.task_config, null, 2))}</pre>`;
}

async function fleetView(){
  const f = await J('dashboard/api/fleet');
  const hb = c => c.heartbeat_age==null ? '—'
    : (c.heartbeat_age < 120 ? `${Math.round(c.heartbeat_age)}s`
                             : `${Math.round(c.heartbeat_age/60)}m`) +
      (c.heartbeat_stale ? ' <span style="color:#9d1c0e">STALE</span>' : '');
  const train = c => {
    const t = c.train;
    if(!t) return '—';
    const parts = [`step ${t.step_time_s}s`,
                   `${Math.round(t.tokens_per_s)} tok/s`];
    if(t.mfu != null) parts.push(`MFU ${(100*t.mfu).toFixed(1)}%`);
    if(t.loss != null) parts.push(`loss ${t.loss.toFixed(3)}`);
    if(t.step != null) parts.push(`@step ${t.step}`);
    return esc(parts.join(', '));
  };
  const host = c => {
    const h = c.host;
    if(!h) return '—';
    const parts = [];
    if(h.disk_used_pct != null) parts.push(`disk ${h.disk_used_pct}%`);
    if(h.framework_procs != null) parts.push(`${h.framework_procs} procs`);
    return esc(parts.join(', '));
  };
  return `<h2>Cluster heartbeats</h2>` + table(
    ['cluster','status','heartbeat','host','training'], f.clusters,
    c=>`<tr><td><a href="#/cluster/${esc(c.name)}">${esc(c.name)}</a></td>
     <td>${B(c.status)}</td><td>${hb(c)}</td><td>${host(c)}</td>
     <td>${train(c)}</td></tr>`) +
  `<h2>Managed-job goodput</h2><div>${goodputLegend}</div>` + table(
    ['job','status','wall','goodput','recoveries','breakdown'], f.jobs,
    g=>`<tr><td><a href="#/job/${g.job_id}">${esc(g.job_id)} ${
       esc(g.name)}</a></td><td>${B(g.status)}</td>
     <td>${g.wall_s.toFixed(1)}s</td>
     <td>${(100*g.goodput_ratio).toFixed(1)}%</td>
     <td>${esc(g.recoveries)}</td>
     <td style="min-width:220px">${goodputBar(g)}</td></tr>`);
}

async function serviceView(name){
  const v = await J('dashboard/api/service/' + encodeURIComponent(name));
  const ready = v.replicas.filter(r=>r.status==='READY').length;
  const st = series[name] = (series[name]||[]);
  st.push(ready);
  if(st.length > 120) st.shift();
  const maxR = Math.max(...st, 1);
  return `<h2>Service ${esc(name)}</h2>` + kv({
      status: B(v.status), endpoint: esc(v.endpoint),
      version: 'v' + (v.version??1),
      'controller pid': esc(v.controller_pid),
      'controller restarts': esc(v.controller_restarts),
      'ready replicas': `${ready}/${v.replicas.length}`}) +
    `<h2>Ready replicas over time</h2>` + sparkline(st, '#0b57d0', maxR) +
    `<h2>Replicas</h2>` + table(
      ['id','status','pool','version','endpoint','cluster','spot',
       'weight','created','health'], v.replicas,
      r=>`<tr><td>${esc(r.replica_id)}</td><td>${B(r.status)}</td>
       <td>${poolCell(r.role)}</td>
       <td>v${r.version??1}</td><td>${esc(r.endpoint)}</td>
       <td>${esc(r.cluster_name)}</td><td>${r.use_spot?'spot':'od'}</td>
       <td>${esc(r.weight)}</td><td>${T(r.created_at)}</td>
       <td>${healthCell(r.health)}</td></tr>`) +
    `<h2>Spec</h2><pre class="log">${
      esc(JSON.stringify(v.spec, null, 2))}</pre>`;
}

// Disaggregated-serving pool role, compacted for the replicas table:
// prefill/decode pools get a colored badge, colocated stays quiet.
function poolCell(role){
  if(role === 'prefill') return '<b style="color:#7a5b00">prefill</b>';
  if(role === 'decode') return '<b style="color:#0a7d33">decode</b>';
  return '—';
}

// Last probe body, compacted: the LLM replica's engine stats become
// "12.3k tok, 5/16 slots, pfx 40%"; anything else shows key count.
function healthCell(h){
  if(!h) return '—';
  const e = h.engine;
  if(e){
    const parts = [`${(e.tokens_emitted||0).toLocaleString()} tok`,
                   `${e.active_slots??0}/${e.slots??'?'} slots`];
    const ps = e.prefix_share;
    if(ps && ps.enabled && (ps.hits + ps.misses) > 0)
      parts.push(`pfx ${ps.hits} hit`);
    const sp = e.speculative;
    if(sp && sp.rounds > 0)
      parts.push(`spec ${Math.round((sp.acceptance_rate||0)*100)}%`);
    // Paged pool block states: free/owned/shared/cached partition the
    // usable pool exactly once blocks are refcount-shared (the old
    // used/usable pair double-counted shared blocks); e.g.
    // "12/30 blk shr4 c6".
    const kb = e.kv_blocks;
    if(kb && kb.usable > 0){
      let t = `${kb.used ?? 0}/${kb.usable} blk`;
      if(kb.shared) t += ` shr${kb.shared}`;
      if(kb.cached) t += ` c${kb.cached}`;
      // Hierarchical tiers: demoted chains living OFF-device — host
      // DRAM (h) and bucket spill segments (d) — next to the device
      // partition, e.g. "12/30 blk shr4 c6 h8 d20".
      if(kb.host) t += ` h${kb.host}`;
      if(kb.spilled) t += ` d${kb.spilled}`;
      parts.push(t);
    }
    // Block-share hit rate once the trie has seen traffic, e.g.
    // "share 72%" (+fork count when CoW forks happened).
    const px = e.prefix_share;
    if(px && px.enabled && (px.hits + px.misses) > 0){
      let t = `share ${Math.round((px.hit_rate||0)*100)}%`;
      if(px.cow_forks) t += ` f${px.cow_forks}`;
      parts.push(t);
    }
    // Prefix-affinity advert (fleet routing): how much of the trie
    // this replica exposes to the LB, e.g. "aff 12/30" = 12 chain
    // entries advertised of 30 resident nodes ("+" = truncated at
    // SKYTPU_PREFIX_SUMMARY_MAX).
    const ps = h.prefix_summary;
    if(ps && ps.entries && ps.entries.length)
      parts.push(`aff ${ps.entries.length}/${ps.nodes??'?'}${
        ps.truncated ? '+' : ''}`);
    // Decode-dispatch pipeline: depth + how much host bookkeeping the
    // in-flight chunk hid (cumulative), e.g. "pipe d1 ovl 1.2s".
    const pl = e.pipeline;
    if(pl && pl.dispatches > 0){
      const ms = pl.pipeline_depth > 0 ? pl.host_overlap_ms
                                       : pl.bubble_ms;
      const t = ms >= 1000 ? `${(ms/1000).toFixed(1)}s`
                           : `${Math.round(ms)}ms`;
      parts.push(`pipe d${pl.pipeline_depth} ${
        pl.pipeline_depth > 0 ? 'ovl' : 'bub'} ${t}`);
    }
    // QoS admission: queue depth + cumulative shed/evict counters,
    // e.g. "q3 shed12 ev1" (serve/qos.py; absent when QoS is off).
    const qo = h.qos;
    if(qo && qo.enabled){
      let t = `q${qo.queue_depth_total||0}`;
      if(qo.shed_total) t += ` shed${qo.shed_total}`;
      if(qo.evicted_total) t += ` ev${qo.evicted_total}`;
      parts.push(t);
    }
    // KV-handoff accounting (disaggregated serving, serve/disagg.py):
    // exports on prefill replicas, imports on decode replicas, plus
    // colocated fallbacks this replica absorbed — e.g. "exp12 imp9 fb1".
    const dg = h.disagg;
    if(dg && (dg.exports || dg.imports || dg.fallbacks_served)){
      let t = [];
      if(dg.exports) t.push(`exp${dg.exports}`);
      if(dg.imports) t.push(`imp${dg.imports}`);
      if(dg.fallbacks_served) t.push(`fb${dg.fallbacks_served}`);
      parts.push(t.join(' '));
    }
    // Runtime profiler (observability/profiler.py; SKYTPU_PROFILE=1):
    // cumulative compiles (+storm count — nonzero means the
    // compile-once-per-shape contract is being violated live), HBM
    // headroom %, and the cold-start ledger total, e.g.
    // "cmp14 STORM2 hbm 12% warm 8.4s".
    const pf = h.profile;
    if(pf && pf.enabled){
      let t = `cmp${pf.compiles_total||0}`;
      if(pf.storms_total) t += ` STORM${pf.storms_total}`;
      const dm = pf.device_memory;
      if(dm && typeof dm.headroom_frac === 'number')
        t += ` hbm ${Math.round(dm.headroom_frac*100)}%`;
      const cs = pf.cold_start;
      if(cs && cs.complete) t += ` warm ${cs.total_s.toFixed(1)}s`;
      parts.push(t);
    }
    if(h.kv_cache === 'int8') parts.push('kv8');
    if(h.quantize) parts.push(h.quantize);  // outer esc covers it
    return esc(parts.join(', '));
  }
  return `<span title="${esc(JSON.stringify(h))}">${
    Object.keys(h).length} field(s)</span>`;
}

// Multi-series line chart over the sampler's ring buffer.
const PALETTE = ['#0b57d0','#0a7d33','#b3261e','#7a5b00','#6d28d9',
                 '#0e7490','#9d174d','#52525b'];
function lineChart(seriesMap, opts){
  const names = Object.keys(seriesMap).filter(
      k => seriesMap[k].some(v => v > 0) || (opts||{}).keepZero);
  if(!names.length) return '<p>(no data yet)</p>';
  const n = Math.max(...names.map(k => seriesMap[k].length));
  if(n < 2) return '<p>(collecting… charts need two samples; the '+
      'sampler daemon ticks every few seconds)</p>';
  const W=680, H=140, P=6;
  // SLO firing-interval annotations (observability/slo.py): translucent
  // bands behind the series, [x0frac, x1frac] of the charted window.
  const bands = ((opts||{}).bands||[]).map(([a,b])=>
    `<rect x="${(P+a*(W-2*P)).toFixed(1)}" y="0" width="${
      Math.max((b-a)*(W-2*P), 2).toFixed(1)}" height="${H}"
      fill="#b3261e" opacity="0.09"/>`).join('');
  const ymax = Math.max(1, ...names.flatMap(k => seriesMap[k]));
  const lines = names.map((k,i)=>{
    const d = seriesMap[k];
    const pts = d.map((v,j)=>
      `${(P+j/(n-1)*(W-2*P)).toFixed(1)},`+
      `${(H-P-(v/ymax)*(H-2*P-14)).toFixed(1)}`);
    return `<polyline fill="none" stroke="${PALETTE[i%PALETTE.length]}"
      stroke-width="1.8" points="${pts.join(' ')}"/>`;
  });
  const legend = names.map((k,i)=>
    `<span style="color:${PALETTE[i%PALETTE.length]};font-size:12px;
      margin-right:10px">&#9632; ${esc(k)} (${
      seriesMap[k][seriesMap[k].length-1]})</span>`).join('');
  return `<svg class="chart" width="${W}" height="${H}">${bands}`+
    `<text x="${W-P}" y="12" font-size="10" fill="#888" `+
    `text-anchor="end">max ${ymax}</text>${lines.join('')}</svg>`+
    `<div>${legend}</div>`;
}

function familySeries(samples, field){
  const keys = new Set();
  samples.forEach(s => Object.keys(s[field]||{}).forEach(k=>keys.add(k)));
  const out = {};
  keys.forEach(k => { out[k] = samples.map(s => (s[field]||{})[k] || 0); });
  return out;
}

async function metricsView(){
  const m = await J('dashboard/api/metrics/history');
  const s = m.samples;
  if(!s.length) return '<p>(no samples yet)</p>';
  // Delta-rate over consecutive samples; `delta(prev, cur)` returns
  // the (already non-negative) count advanced between them.
  const rateSeries = (delta) => {
    const out = [];
    for(let i=1;i<s.length;i++){
      const dt = Math.max(s[i].ts - s[i-1].ts, 1e-9);
      out.push(Math.max(0, delta(s[i-1], s[i]))/dt);
    }
    return out;
  };
  const sumv = o => Object.values(o||{}).reduce((x,y)=>x+y,0);
  // Request RATE: per-op cumulative counter deltas between samples.
  const rate = rateSeries((a,b)=>
      sumv(b.requests_total_by_op) - sumv(a.requests_total_by_op));
  // Serving token RATE: per-REPLICA clamped deltas summed, so one
  // replica's restart (counter reset) or a scale-down zeroes only its
  // own contribution instead of cratering the fleet rate; a replica's
  // first appearance contributes 0 (no baseline).
  const tokRate = rateSeries((a,b)=>{
    const pa=a.serve_tokens_by_replica||{}, pb=b.serve_tokens_by_replica||{};
    let d=0;
    for(const k in pb) d += Math.max(0, pb[k] - (pa[k] ?? pb[k]));
    return d;
  });
  // QoS shed/evict RATE: per-replica clamped counter deltas, same
  // restart-reset handling as the token rate above.
  const qosRate = (field) => rateSeries((a,b)=>{
    const pa=a.serve_qos_by_replica||{}, pb=b.serve_qos_by_replica||{};
    let d=0;
    for(const k in pb){
      const base = pa[k] ? (pa[k][field]||0) : (pb[k][field]||0);
      d += Math.max(0, (pb[k][field]||0) - base);
    }
    return d;
  });
  const anyQos = s.some(x=>Object.keys(x.serve_qos_by_replica||{}).length);
  const span = s.length > 1 ?
      ((s[s.length-1].ts - s[0].ts)/60).toFixed(1) + ' min' : '';
  // SLO firing intervals overlaid on every chart: [fired_at,
  // resolved_at-or-now] clipped to the charted sample window
  // (observability/slo.py; disabled/unreachable engine = no bands).
  let alerts = {alerts: [], history: []};
  try{ alerts = await J('dashboard/api/alerts'); }catch(e){}
  const t0 = s[0].ts, t1 = s[s.length-1].ts, dt = Math.max(t1 - t0, 1e-9);
  const bands = [];
  const firingNow = [];
  for(const a of (alerts.alerts||[]).concat(alerts.history||[])){
    if(!a.fired_at) continue;
    if(a.state === 'firing') firingNow.push(a);
    const b0 = Math.max((a.fired_at - t0)/dt, 0);
    const b1 = Math.min(((a.resolved_at||t1) - t0)/dt, 1);
    if(b1 > 0 && b0 < 1) bands.push([b0, b1]);
  }
  const LC = (m, o) => lineChart(m, Object.assign({bands}, o||{}));
  const alertLine = firingNow.length ?
    `<p><a href="#/alerts">${firingNow.length} SLO alert(s) firing</a>: ` +
    firingNow.slice(0,6).map(a=>`${B(a.severity)} ${esc(a.rule)} on ${
      esc(a.target)}`).join(' · ') + '</p>' : '';
  return `<h2>Fleet metrics <span id="ts2" style="color:#888;font-size:12px">
      ${s.length} samples over ${span}${bands.length ?
      '; red bands = SLO alert firing intervals' : ''}</span></h2>` +
    alertLine +
    `<h2>Clusters by status</h2>` +
      LC(familySeries(s, 'clusters')) +
    `<h2>Managed jobs by status</h2>` +
      LC(familySeries(s, 'managed_jobs')) +
    `<h2>Services by status</h2>` +
      LC(familySeries(s, 'services')) +
    `<h2>Serve replicas</h2>` +
      LC({ready: s.map(x=>x.replicas_ready||0),
          total: s.map(x=>x.replicas_total||0)}) +
    `<h2>Serving throughput (tok/s)</h2>` +
      LC({'tok/s': tokRate.map(v=>Math.round(v*10)/10)},
         {keepZero:true}) +
    (anyQos ? `<h2>Serve QoS queue depth</h2>` +
      LC({queued: s.map(x=>x.serve_queue_depth||0)},
         {keepZero:true}) +
    `<h2>Serve QoS shed / evict rate (1/s)</h2>` +
      LC({shed: qosRate('shed').map(v=>Math.round(v*100)/100),
          evicted: qosRate('evicted').map(v=>Math.round(v*100)/100)},
         {keepZero:true}) : '') +
    `<h2>API requests by status</h2>` +
      LC(familySeries(s, 'requests')) +
    `<h2>API request rate (req/s)</h2>` +
      LC({'req/s': rate.map(v=>Math.round(v*100)/100)},
         {keepZero:true});
}

// SLO alert panel (observability/slo.py): active pending/firing alerts,
// resolved history, and the declared rule catalog with burn-rate
// parameters. Page-severity breaches link to #/incidents — the engine
// froze a black-box bundle (trigger slo_breach) when they fired.
async function alertsView(){
  const d = await J('dashboard/api/alerts');
  const head = `<h2>SLO alerts <span style="color:#888;font-size:12px">${
    d.enabled ? 'evaluator on' :
    'evaluator DISABLED (set SKYTPU_SLO=1 on the API server)'}; page
    breaches freeze incident bundles — see <a href="#/incidents">
    incidents</a></span></h2>`;
  const when = a => a.fired_at ? T(a.fired_at) : T(a.started_at);
  const burn = a => `${Math.round((a.fast_frac||0)*100)}% / ${
    Math.round((a.slow_frac||0)*100)}%`;
  const val = a => `${a.value!=null ? (+a.value).toFixed(1) : '-'} ${
    esc(a.op)} ${a.threshold}`;
  const active = table(
    ['rule','severity','target','state','value vs threshold',
     'burn fast/slow','since'], d.alerts||[],
    a=>`<tr><td>${esc(a.rule)}</td><td>${B(a.severity)}</td>
     <td>${esc(a.target)}</td><td>${B(a.state)}</td>
     <td>${val(a)}</td><td>${burn(a)}</td><td>${when(a)}</td></tr>`);
  const hist = table(
    ['rule','severity','target','fired','resolved','paged'],
    d.history||[],
    a=>`<tr><td>${esc(a.rule)}</td><td>${B(a.severity)}</td>
     <td>${esc(a.target)}</td><td>${T(a.fired_at)}</td>
     <td>${T(a.resolved_at)}</td><td>${a.paged?'bundle':''}</td></tr>`);
  const rules = table(
    ['rule','severity','signal','breach','fast window','slow window'],
    d.rules||[],
    r=>`<tr><td title="${esc(r.doc)}">${esc(r.name)}</td>
     <td>${B(r.severity)}</td><td>${esc(r.signal)}</td>
     <td>${esc(r.op)} ${r.threshold}</td>
     <td>${r.fast_s}s @ ${Math.round(r.fast_burn*100)}%</td>
     <td>${r.slow_s}s @ ${Math.round(r.slow_burn*100)}%</td></tr>`);
  return head + active + `<h2>Resolved (recent)</h2>` + hist +
    `<h2>Rule catalog</h2>` + rules;
}

// Self-healing audit: every remediation decision (acted, observed,
// suppressed) with its phase timings; the trace id links into the
// autopsy view (retained verdict 'remediation').
async function remediationView(){
  const d = await J('dashboard/api/remediation');
  const head = `<h2>Self-healing remediation <span style="color:#888;
    font-size:12px">mode ${esc(d.mode)}${d.mode==='off' ?
    ' (set SKYTPU_REMEDIATE=observe|act on the controller)' : ''}
    </span></h2>`;
  const phases = r => (r.phases||[]).map(
    p=>`${esc(p.name)} ${(p.dt*1000).toFixed(0)}ms`).join(' → ');
  const recs = table(
    ['when','service','action','trigger','outcome','victim','successor',
     'phases','trace'], d.records||[],
    r=>`<tr><td>${T(r.ts)}</td><td>${esc(r.service)}</td>
     <td>${B(r.action)}${r.intended ? ' ('+esc(r.intended)+')' : ''}</td>
     <td>${esc(r.trigger)}</td><td>${B(r.outcome)}</td>
     <td>${r.victim!=null ? esc(r.victim) : ''}</td>
     <td>${r.successor!=null ? esc(r.successor) : ''}</td>
     <td style="font-size:11px;color:#666">${phases(r)}</td>
     <td>${r.trace_id ? `<a href="#/autopsy/${esc(r.trace_id)}">${
       esc(r.trace_id.slice(0,12))}</a>` : ''}</td></tr>`);
  const actions = table(['action','meaning'], d.actions||[],
    a=>`<tr><td>${esc(a.name)}</td><td>${esc(a.doc)}</td></tr>`);
  return head + recs + `<h2>Action registry</h2>` + actions;
}

// Waterfall of one completed trace: rows indented by span depth, bars
// positioned by (start - trace start) / duration. Spans arrive sorted
// by start from /debug/traces.
function waterfall(tr){
  const t0 = tr.start, dur = Math.max(tr.duration_ms, 0.01);
  const byId = {};
  tr.spans.forEach(s => { byId[s.span_id] = s; });
  const rows = tr.spans.map(s => {
    let d = 0, p = byId[s.parent_id], guard = 0;
    while(p && guard++ < 12){ d++; p = byId[p.parent_id]; }
    const ms = ((s.end ?? s.start) - s.start) * 1000;
    const left = Math.max(Math.min((s.start - t0) * 1000 / dur * 100, 100), 0);
    const w = Math.max(Math.min(ms / dur * 100, 100 - left), 0.4);
    const a = s.attrs || {};
    const extra = ['tokens','row','host_overlap_ms','bubble_ms','error']
      .filter(k => a[k] !== undefined).map(k => `${k}=${a[k]}`).join(' ');
    return `<tr><td style="padding-left:${8+d*14}px;white-space:nowrap">${
       esc(s.name)}</td>
     <td style="width:55%"><div style="position:relative;height:12px;
       background:#f0f0f3;border-radius:2px"><div title="${esc(extra)}"
       style="position:absolute;left:${left.toFixed(2)}%;width:${
       w.toFixed(2)}%;height:12px;border-radius:2px;background:${
       PALETTE[d % PALETTE.length]}"></div></div></td>
     <td style="color:#666;white-space:nowrap">${ms.toFixed(1)} ms</td>
     <td style="color:#999;font-size:11px">${esc(extra)}</td></tr>`;
  }).join('');
  const a = tr.attrs || {};
  const tags = [tr.trace_id.slice(0,16), a.qos_class, a.tenant,
                a.request_id, a.ttft_ms !== undefined ?
                `ttft ${a.ttft_ms}ms` : null]
    .filter(Boolean).map(esc).join(' · ');
  // Retention badge + autopsy link: kept journeys are the interesting
  // 0.1% — the badge names WHY retention kept this one.
  const kept = tr.retained
    ? ` ${B('kept:' + tr.retained)}
       <a href="#/autopsy/${esc(tr.trace_id)}" style="font-size:12px
       ">autopsy</a>` : '';
  return `<h2>${esc(tr.name)} — ${tr.duration_ms.toFixed(1)} ms${kept}
    <span style="color:#888;font-weight:400;font-size:12px">${tags}</span>
    </h2><table>${rows}</table>`;
}

// Request autopsy: one kept trace's where-time-went (queue / prefill /
// handoff / decode / stream) next to its QoS class's baseline — the
// "why was THIS one slow" view /debug/traces?autopsy=1 computes
// server-side (observability/trace.py phase_breakdown).
async function autopsyView(traceId){
  const d = await J('debug/traces?autopsy=1&trace_id=' +
                    encodeURIComponent(traceId));
  if(!(d.autopsy||[]).length || !d.traces.length)
    return `<h2>Autopsy</h2><p>(trace ${esc(traceId.slice(0,16))} not
      found — it may have rotated out; retained traces survive in the
      keep-* spool and incident bundles)</p>`;
  const a = d.autopsy[0], tr = d.traces[0];
  const phases = ['queue','prefill','handoff','decode','stream','other'];
  const base = a.baseline || {};
  const maxMs = Math.max(...phases.map(p => Math.max(
      a.breakdown[p]||0, base[p]||0)), 0.01);
  const rows = phases.filter(p =>
      (a.breakdown[p]||0) > 0 || (base[p]||0) > 0).map(p => {
    const ms = a.breakdown[p]||0, bms = base[p]||0;
    const w = (ms/maxMs*100).toFixed(1), bw = (bms/maxMs*100).toFixed(1);
    return `<tr><td>${esc(p)}</td>
     <td style="width:45%"><div style="height:12px;background:#f0f0f3;
       border-radius:2px"><div style="width:${w}%;height:12px;
       border-radius:2px;background:${PALETTE[0]}"></div></div></td>
     <td style="color:#666;white-space:nowrap">${ms.toFixed(1)} ms</td>
     <td style="width:25%"><div style="height:8px;background:#f0f0f3;
       border-radius:2px"><div style="width:${bw}%;height:8px;
       border-radius:2px;background:#bbb"></div></div></td>
     <td style="color:#999;white-space:nowrap">${bms.toFixed(1)} ms
       baseline</td></tr>`;
  }).join('');
  return `<h2>Autopsy — ${esc(tr.name)} ${
    a.retained ? B('kept:' + a.retained) : ''}
    <span style="color:#888;font-weight:400;font-size:12px">${
    esc(tr.trace_id.slice(0,16))} · ${esc(a.qos_class)} · ${
    tr.duration_ms.toFixed(1)} ms vs class baseline ${
    (base.total||0).toFixed(1)} ms (n=${base.n||0})</span></h2>
    <table><tr><th>phase</th><th>this request</th><th></th>
    <th>class baseline</th><th></th></tr>${rows}</table>` +
    d.traces.map(waterfall).join('');
}

async function tracesView(traceId){
  const d = await J(traceId
      ? 'debug/traces?trace_id=' + encodeURIComponent(traceId)
      : 'debug/traces?slowest=1&limit=10');
  if(!d.traces.length)
    return '<h2>Traces</h2><p>(no ' +
      (traceId ? `trace ${esc(traceId.slice(0,16))} in the ring — it `+
                 'may have rotated out; the incident bundle retains '+
                 'its frozen copy' : 'completed traces yet' +
      (d.enabled ? '' : ' — tracing is disabled, set SKYTPU_TRACE=1')) +
      ')</p>';
  return `<h2>${traceId ? 'Trace ' + esc(traceId.slice(0,16))
    : 'Slowest recent traces'} <span style="color:#888;font-size:12px
    ">ring of completed traces; filter via /debug/traces?trace_id=…
    </span></h2>` + d.traces.map(waterfall).join('');
}

// Incident panel (observability/blackbox.py): the API-server host's
// bundle spool. Each bundle links to its full JSON and — via the trace
// ids frozen inside it — to the trace waterfall.
async function incidentsView(){
  const d = await J('dashboard/api/incidents');
  const head = `<h2>Incident bundles <span style="color:#888;
    font-size:12px">${esc(d.dir)}${d.enabled ? '' :
    ' — recorder DISABLED (SKYTPU_BLACKBOX=0)'}; replica-local bundles:
    replica /debug/blackbox or 'stpu debug dump &lt;cluster&gt;'
    </span></h2>`;
  if(!d.bundles.length)
    return head + '<p>(no incident bundles — nothing has gone wrong ' +
      'on this host, or nothing dumped yet)</p>';
  return head + table(
    ['when','process','trigger','events','reason','traces',''],
    d.bundles,
    b=>`<tr><td>${T(b.ts)}</td><td>${esc(b.proc)}[${esc(b.pid)}]</td>
     <td>${B(b.trigger)}</td><td>${esc(b.events)}</td>
     <td>${esc(b.reason)}</td>
     <td>${(b.trace_ids||[]).map(t=>
        `<a href="#/traces/${esc(t)}">${esc(t.slice(0,12))}</a>`)
        .join(' ')}</td>
     <td><a href="#/incidents/${esc(b.file)}">open</a></td></tr>`);
}

async function incidentView(file){
  let b = null;
  try{
    b = await J('dashboard/api/incident/' + encodeURIComponent(file));
  }catch(e){ /* 404 = rotated out */ }
  if(!b)
    return `<h2>Bundle ${esc(file)}</h2><p>(not in the spool — it may
      have rotated out; bundles keep the newest SKYTPU_BLACKBOX_KEEP
      files)</p>`;
  const evs = (b.events||[]).slice(-100).reverse();
  const open_ = ((b.traces||{}).open)||[];
  return `<h2>Bundle ${esc(file)}</h2>` + kv({
      when: T(b.ts), process: `${esc(b.proc)}[${esc(b.pid)}]`,
      trigger: B(b.trigger), reason: esc(b.reason),
      events: esc((b.events||[]).length),
      'open traces at dump': esc(open_.length)}) +
    `<h2>Ring (newest first)</h2>` + table(
      ['t','event','attrs'], evs,
      e=>`<tr><td>${T(e.ts)}</td><td>${esc(e.name)}</td>
       <td><code style="font-size:11px">${
         esc(JSON.stringify(e.attrs||{}))}</code></td></tr>`) +
    (open_.length ? `<h2>Open traces at dump time</h2>` +
      open_.map(t=>`<p><a href="#/traces/${esc(t.trace_id)}">${
        esc(t.trace_id.slice(0,16))}</a> ${esc(t.name)} — open ${
        (t.open_ms/1000).toFixed(1)}s</p>`).join('') : '') +
    `<h2>Thread stacks</h2><pre class="log">${
      esc(b.stacks||'(none captured)')}</pre>` +
    `<h2>Env flags</h2><pre class="log">${
      esc(JSON.stringify(b.env_flags||{}, null, 2))}</pre>`;
}

async function logsView(query){
  let results = '';
  if(query){
    const r = await J('dashboard/api/logs/search?q=' +
                      encodeURIComponent(query));
    results = `<p style="color:#888;font-size:12px">${r.matches.length}
        match(es) over ${r.files_scanned} file(s)${
        r.truncated ? ' (truncated)' : ''}</p>` +
      table(['cluster','job','file','line','text'], r.matches,
        m=>`<tr><td><a href="#/cluster/${esc(m.cluster)}">${
         esc(m.cluster)}</a></td><td>${esc(m.job_id)}</td>
         <td>${esc(m.file)}</td><td>${esc(m.line_no)}</td>
         <td><code style="font-size:12px">${esc(m.line)}</code></td></tr>`);
  }
  // Enter submits by updating the hash; the router re-renders.
  return `<h2>Log search</h2>
    <input id="logq" value="${esc(query||'')}" placeholder="substring…"
      style="width:420px;padding:6px;font-size:13px"
      onkeydown="if(event.key==='Enter')
        location.hash='#/logs/'+encodeURIComponent(this.value)">
    ${results}`;
}

async function infraView(){
  const i = await J('dashboard/api/infra');
  return '<h2>Clouds</h2>' + table(['cloud','enabled','reason'], i.clouds,
      c=>`<tr><td>${esc(c.name)}</td>
       <td>${B(c.enabled ? 'ALIVE' : 'DONE')}</td>
       <td>${esc(c.reason||'')}</td></tr>`) +
    '<h2>Catalogs</h2>' + table(['file','rows','age (days)'], i.catalogs,
      c=>`<tr><td>${esc(c.file)}</td><td>${esc(c.rows)}</td>
       <td>${esc(c.age_days)}</td></tr>`) +
    '<h2>API server</h2>' + kv(Object.fromEntries(
      Object.entries(i.server).map(([k,v])=>[k, esc(v)])));
}

async function configView(){
  const c = await J('dashboard/api/config');
  return `<h2>Config <span style="color:#888;font-size:12px">${
      esc(c.loaded_from || '(defaults only)')}</span></h2>` +
    `<pre class="log">${esc(JSON.stringify(c.config, null, 2))}</pre>`;
}

async function usersView(){
  const u = await J('dashboard/api/users');
  return '<h2>Users</h2>' + table(['name','role','created'], u,
    x=>`<tr><td>${esc(x.name)}</td><td>${esc(x.role)}</td>
     <td>${T(x.created_at)}</td></tr>`);
}

async function workspacesView(){
  const w = await J('dashboard/api/workspaces');
  return '<h2>Workspaces</h2>' + table(
    ['name','clusters','created by','created'], w,
    x=>`<tr><td>${esc(x.name)}</td><td>${esc(x.clusters)}</td>
     <td>${esc(x.created_by)}</td><td>${T(x.created_at)}</td></tr>`);
}

async function route(){
  const h = location.hash || '#/';
  let html;
  try{
    let m;
    if((m = h.match(/^#\\/cluster\\/(.+)$/)))
      html = await clusterView(decodeURIComponent(m[1]));
    else if((m = h.match(/^#\\/job\\/(\\d+)$/))) html = await jobView(m[1]);
    else if((m = h.match(/^#\\/service\\/(.+)$/)))
      html = await serviceView(decodeURIComponent(m[1]));
    else if(h === '#/users') html = await usersView();
    else if(h === '#/workspaces') html = await workspacesView();
    else if(h === '#/metrics') html = await metricsView();
    else if(h === '#/alerts') html = await alertsView();
    else if(h === '#/remediation') html = await remediationView();
    else if((m = h.match(/^#\\/traces\\/(.+)$/)))
      html = await tracesView(decodeURIComponent(m[1]));
    else if(h === '#/traces') html = await tracesView();
    else if((m = h.match(/^#\\/autopsy\\/(.+)$/)))
      html = await autopsyView(decodeURIComponent(m[1]));
    else if((m = h.match(/^#\\/incidents\\/(.+)$/)))
      html = await incidentView(decodeURIComponent(m[1]));
    else if(h === '#/incidents') html = await incidentsView();
    else if(h === '#/fleet') html = await fleetView();
    else if((m = h.match(/^#\\/logs(?:\\/(.*))?$/)))
      html = await logsView(m[1] ? decodeURIComponent(m[1]) : '');
    else if(h === '#/infra') html = await infraView();
    else if(h === '#/config') html = await configView();
    else html = await overview();
    document.getElementById('ts').textContent =
        'updated ' + new Date().toLocaleTimeString();
  }catch(e){ html = `<p>error: ${esc(e.message)}</p>`; }
  document.getElementById('view').innerHTML = html;
}
window.addEventListener('hashchange', route);
route();
// Auto-refresh everywhere EXCEPT the log-search view: re-rendering
// would wipe the query box mid-typing.
setInterval(() => {
  if(!(location.hash||'').startsWith('#/logs')) route();
}, 2000);
</script></body></html>"""


async def page(request: web.Request) -> web.Response:
    del request
    return web.Response(text=_PAGE, content_type='text/html')
