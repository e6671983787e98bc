"""CLI: the `stpu` command.

Reference analog: ``sky/client/cli/command.py`` (6,921 LoC click CLI).  Same
verb surface: launch/exec/status/queue/logs/cancel/stop/start/down/autostop/
check/show-tpus/cost-report, plus `jobs` and `serve` sub-groups (wired as
their planes land).
"""
from __future__ import annotations

import datetime as _dt
import os
import sys
from typing import List, Optional, Tuple

import click

from skypilot_tpu import exceptions


def _clean_errors(f):
    """Render framework errors as one-line CLI errors, not tracebacks."""
    import functools

    @functools.wraps(f)
    def wrapper(*args, **kwargs):
        try:
            return f(*args, **kwargs)
        except exceptions.SkyTpuError as e:
            raise click.ClickException(str(e)) from e

    return wrapper


def _echo_table(rows: List[dict], columns: List[Tuple[str, str]]) -> None:
    if not rows:
        click.echo('(none)')
        return
    widths = {key: max(len(header), *(len(str(r.get(key, ''))) for r in rows))
              for key, header in columns}
    header = '  '.join(h.ljust(widths[k]) for k, h in columns)
    click.echo(click.style(header, bold=True))
    for r in rows:
        click.echo('  '.join(
            str(r.get(k, '')).ljust(widths[k]) for k, _ in columns))


def _load_task(entrypoint: Tuple[str, ...], name: Optional[str],
               workdir: Optional[str], cloud: Optional[str],
               accelerators: Optional[str], num_nodes: Optional[int],
               use_spot: Optional[bool], envs: Tuple[Tuple[str, str], ...],
               secrets: Tuple[str, ...]):
    from skypilot_tpu.resources import Resources
    from skypilot_tpu.task import Task
    if entrypoint and entrypoint[0].endswith(('.yaml', '.yml')):
        task = Task.from_yaml(entrypoint[0])
    elif entrypoint:
        task = Task(run=' '.join(entrypoint))
    else:
        raise click.UsageError('Provide a task YAML or an inline command.')
    if name:
        task.name = name
    if workdir:
        task.workdir = workdir
    if num_nodes:
        task.num_nodes = num_nodes
    overrides = {}
    if cloud:
        overrides['cloud'] = cloud
    if accelerators:
        overrides['accelerators'] = accelerators
    if use_spot is not None:
        overrides['use_spot'] = use_spot
    if overrides:
        task.set_resources([r.copy(**overrides)
                            for r in task.resources_ordered])
    if envs:
        task.update_envs(dict(envs))
    for s in secrets:
        if '=' in s:
            k, v = s.split('=', 1)
        else:
            k, v = s, os.environ.get(s, '')
        task.update_secrets({k: v})
    return task


def _common_task_options(f):
    f = click.option('--name', '-n', default=None)(f)
    f = click.option('--workdir', default=None,
                     type=click.Path(exists=True, file_okay=False))(f)
    f = click.option('--cloud', default=None)(f)
    f = click.option('--gpus', '--tpus', 'accelerators', default=None,
                     help='Accelerator spec, e.g. tpu-v5e-16')(f)
    f = click.option('--num-nodes', type=int, default=None,
                     help='Number of slices (multislice when > 1)')(f)
    f = click.option('--use-spot/--no-use-spot', default=None)(f)
    f = click.option('--env', 'envs', multiple=True,
                     type=(str, str))(f)
    f = click.option('--secret', 'secrets', multiple=True)(f)
    return f


@click.group()
@click.version_option('0.1.0', prog_name='stpu')
def cli() -> None:
    """skypilot_tpu: TPU-native cluster orchestration."""


@cli.command()
@click.argument('entrypoint', nargs=-1)
@click.option('--cluster', '-c', default=None)
@click.option('--detach-run', '-d', is_flag=True, default=False)
@click.option('--retry-until-up', is_flag=True, default=False)
@click.option('--idle-minutes-to-autostop', '-i', type=int, default=None)
@click.option('--down', is_flag=True, default=False)
@click.option('--dryrun', is_flag=True, default=False)
@_common_task_options
@_clean_errors
def launch(entrypoint, cluster, detach_run, retry_until_up,
           idle_minutes_to_autostop, down, dryrun, name, workdir, cloud,
           accelerators, num_nodes, use_spot, envs, secrets):
    """Provision a cluster (TPU slice or VM) and run a task on it."""
    from skypilot_tpu import execution
    task = _load_task(entrypoint, name, workdir, cloud, accelerators,
                      num_nodes, use_spot, envs, secrets)
    job_id, handle = execution.launch(
        task, cluster_name=cluster, retry_until_up=retry_until_up,
        idle_minutes_to_autostop=idle_minutes_to_autostop, down=down,
        detach_run=detach_run, dryrun=dryrun)
    if handle is not None:
        click.echo(f'Cluster: {handle.cluster_name} '
                   f'(job {job_id if job_id is not None else "-"})')


@cli.command('exec')
@click.argument('cluster')
@click.argument('entrypoint', nargs=-1)
@click.option('--detach-run', '-d', is_flag=True, default=False)
@_common_task_options
@_clean_errors
def exec_cmd(cluster, entrypoint, detach_run, name, workdir, cloud,
             accelerators, num_nodes, use_spot, envs, secrets):
    """Run a task on an existing cluster (no provisioning/setup)."""
    from skypilot_tpu import execution
    task = _load_task(entrypoint, name, workdir, cloud, accelerators,
                      num_nodes, use_spot, envs, secrets)
    job_id, _ = execution.exec_(task, cluster, detach_run=detach_run)
    click.echo(f'Job {job_id} submitted to {cluster}.')


def _format_heartbeat(row: dict) -> str:
    """Heartbeat-age cell: '-' before the first heartbeat; 'STALE!' when
    older than 3 daemon intervals (core.status computes the flag)."""
    age = row.get('heartbeat_age')
    if age is None:
        return '-'
    text = f'{int(age)}s' if age < 120 else f'{int(age / 60)}m'
    return f'{text} STALE!' if row.get('heartbeat_stale') else text


@cli.command()
@click.option('--refresh', '-r', is_flag=True, default=False)
@click.option('--all-workspaces', '-a', is_flag=True, default=False,
              help='Show clusters from every workspace.')
@_clean_errors
def status(refresh, all_workspaces):
    """Show clusters (active workspace unless --all-workspaces)."""
    from skypilot_tpu import core
    rows = core.status(refresh=refresh, all_workspaces=all_workspaces)
    for r in rows:
        r['heartbeat'] = _format_heartbeat(r)
    cols = [('name', 'NAME'), ('status', 'STATUS'),
            ('cloud', 'CLOUD'), ('region', 'REGION'),
            ('resources', 'RESOURCES'), ('nodes', 'NODES'),
            ('workers', 'WORKERS'), ('autostop', 'AUTOSTOP'),
            ('heartbeat', 'HEARTBEAT')]
    if all_workspaces:
        cols.insert(1, ('workspace', 'WORKSPACE'))
    _echo_table(rows, cols)
    stale = [r['name'] for r in rows if r.get('heartbeat_stale')]
    if stale:
        click.echo(click.style(
            f'Stale heartbeat (> 3 intervals): {", ".join(stale)} — the '
            'cluster daemon may be dead or the host wedged.', fg='yellow'))


@cli.command()
@click.argument('cluster')
@_clean_errors
def queue(cluster):
    """Show a cluster's job queue."""
    from skypilot_tpu import core
    rows = core.queue(cluster)
    _echo_table(rows, [('job_id', 'ID'), ('name', 'NAME'),
                       ('status', 'STATUS'), ('num_workers', 'WORKERS'),
                       ('submitted_at', 'SUBMITTED')])


@cli.command()
@click.argument('cluster')
@click.argument('job_id', required=False, type=int)
@click.option('--no-follow', is_flag=True, default=False)
@_clean_errors
def logs(cluster, job_id, no_follow):
    """Tail a job's logs."""
    from skypilot_tpu import core
    core.tail_logs(cluster, job_id, follow=not no_follow)


@cli.command()
@click.argument('cluster')
@click.argument('job_id', required=False, type=int)
@_clean_errors
def cancel(cluster, job_id):
    """Cancel a job."""
    from skypilot_tpu import core
    ok = core.cancel(cluster, job_id)
    click.echo('Cancelled.' if ok else 'Nothing to cancel.')


@cli.command()
@click.argument('clusters', nargs=-1, required=True)
@click.option('--yes', '-y', is_flag=True, default=False)
@_clean_errors
def down(clusters, yes):
    """Terminate clusters."""
    from skypilot_tpu import core
    for c in clusters:
        if not yes:
            click.confirm(f'Terminate cluster {c}?', abort=True)
        core.down(c)
        click.echo(f'Terminated {c}.')


@cli.command()
@click.argument('clusters', nargs=-1, required=True)
@_clean_errors
def stop(clusters):
    """Stop clusters (restartable with `stpu start`)."""
    from skypilot_tpu import core
    for c in clusters:
        core.stop(c)
        click.echo(f'Stopped {c}.')


@cli.command()
@click.argument('cluster')
@_clean_errors
def start(cluster):
    """Restart a stopped cluster."""
    from skypilot_tpu import core
    core.start(cluster)
    click.echo(f'Started {cluster}.')


@cli.command()
@click.argument('cluster')
@click.option('--idle-minutes', '-i', type=int, required=True,
              help='-1 cancels autostop')
@click.option('--down', is_flag=True, default=False)
@_clean_errors
def autostop(cluster, idle_minutes, down):
    """Schedule automatic stop/down after idleness."""
    from skypilot_tpu import core
    core.autostop(cluster, idle_minutes, down=down)
    click.echo(f'Autostop set on {cluster}: {idle_minutes}m '
               f'({"down" if down else "stop"}).')


@cli.command()
@_clean_errors
def check():
    """Check cloud credentials."""
    from skypilot_tpu import check as check_lib
    results = check_lib.check_capabilities(quiet=False)
    if not any(ok for ok, _ in results.values()):
        sys.exit(1)


@cli.command()
@click.option('--timeout', default=90.0, show_default=True,
              help='Backend-init probe timeout (seconds).')
@click.option('--no-probe', is_flag=True,
              help='Skip the backend probe: process table only.')
@click.option('--reap', is_flag=True,
              help='Kill session-owned (fingerprinted) stray daemons.')
@click.option('--reap-all', is_flag=True,
              help='Kill ALL framework daemons, fingerprinted or not.')
@_clean_errors
def doctor(timeout, no_probe, reap, reap_all):
    """Diagnose TPU backend health: backend probe (an ordinary child,
    killed at its timeout) and stray framework daemons — one that
    touched jax holds the chip (see utils/tpu_doctor.py)."""
    import json as _json

    from skypilot_tpu.utils import tpu_doctor
    if reap or reap_all:
        res = tpu_doctor.reap_stray_processes(reap_all=reap_all)
        click.echo(f"Reaped {len(res['reaped'])} stray process(es); "
                   f"spared {len(res['spared'])} unfingerprinted.",
                   err=True)
    report = tpu_doctor.doctor_report(timeout, probe=not no_probe)
    click.echo(_json.dumps(report, indent=2))
    if not no_probe and not report['probe']['ok']:
        sys.exit(1)


@cli.command()
@_clean_errors
def dashboard():
    """Print (and try to open) the API server's dashboard URL."""
    from urllib.parse import quote

    from skypilot_tpu.client import sdk as sdk_lib
    sdk_lib.ensure_server()
    url = f'{sdk_lib.server_url()}/dashboard'
    token = os.environ.get('SKYTPU_API_TOKEN')
    if token:
        # Percent-encode: URLSearchParams decodes '+' and splits on '&'.
        url += f'?token={quote(token, safe="")}'
    click.echo(url)
    import webbrowser
    try:
        webbrowser.open(url)
    except Exception:  # noqa: BLE001 — headless host: URL printed above
        pass


@cli.command('show-tpus')
@click.option('--name-filter', default=None)
@click.option('--region', default=None)
@_clean_errors
def show_tpus(name_filter, region):
    """List TPU slice offerings and prices (analog of `sky show-gpus`)."""
    from skypilot_tpu.catalog import gcp_catalog
    df = gcp_catalog.list_accelerators(name_filter, region)
    rows = df.to_dict('records')
    _echo_table(rows, [('AcceleratorName', 'ACCELERATOR'),
                       ('Topology', 'TOPOLOGY'), ('Hosts', 'HOSTS'),
                       ('Region', 'REGION'),
                       ('AvailabilityZone', 'ZONE'),
                       ('Price', '$/HR'), ('SpotPrice', '$/HR(SPOT)')])


@cli.command('cost-report')
@_clean_errors
def cost_report():
    """Estimated accumulated cost per cluster."""
    from skypilot_tpu import core
    _echo_table(core.cost_report(),
                [('name', 'NAME'), ('duration_hours', 'HOURS'),
                 ('price_per_hour', '$/HR'), ('cost', 'COST($)')])



@cli.group('jobs')
def jobs_group():
    """Managed jobs with automatic recovery (analog of `sky jobs`)."""


@jobs_group.command('launch')
@click.argument('entrypoint', nargs=-1)
@click.option('--recovery', default='FAILOVER',
              type=click.Choice(['FAILOVER', 'EAGER_FAILOVER']))
@click.option('--max-restarts-on-errors', type=int, default=0)
@_common_task_options
@_clean_errors
def jobs_launch(entrypoint, recovery, max_restarts_on_errors, name, workdir,
                cloud, accelerators, num_nodes, use_spot, envs, secrets):
    """Submit a managed job (auto-recovers from preemption)."""
    from skypilot_tpu import jobs
    task = _load_task(entrypoint, name, workdir, cloud, accelerators,
                      num_nodes, use_spot, envs, secrets)
    job_id = jobs.launch(task, recovery_strategy=recovery,
                         max_restarts_on_errors=max_restarts_on_errors)
    click.echo(f'Managed job {job_id} submitted '
               f'(strategy={recovery}). Track: stpu jobs queue')


@jobs_group.command('queue')
@click.option('--all-workspaces', '-a', is_flag=True, default=False,
              help='Show managed jobs from every workspace.')
@_clean_errors
def jobs_queue(all_workspaces):
    """List managed jobs (active workspace unless --all-workspaces)."""
    from skypilot_tpu import jobs
    cols = [('job_id', 'ID'), ('name', 'NAME'), ('status', 'STATUS'),
            ('cluster', 'CLUSTER'), ('recoveries', 'RECOVERIES')]
    if all_workspaces:
        cols.insert(1, ('workspace', 'WORKSPACE'))
    _echo_table(jobs.queue(all_workspaces=all_workspaces), cols)


@jobs_group.command('goodput')
@click.argument('job_id', type=int)
@_clean_errors
def jobs_goodput(job_id):
    """Goodput/badput breakdown for a managed job: how much of the
    wall-clock was productive compute (RUNNING) vs. provisioning,
    queueing, and recovery — from the phase ledger."""
    from skypilot_tpu import jobs
    g = jobs.goodput(job_id)
    if g is None:
        raise click.ClickException(
            f'managed job {job_id} not found (or predates the ledger)')
    wall = max(g['wall_s'], 1e-9)
    click.echo(f"Managed job {job_id} ({g['status']}"
               f"{'' if g['closed'] else ', still running'}): "
               f"wall-clock {g['wall_s']:.1f}s, "
               f"goodput {100 * g['goodput_ratio']:.1f}%, "
               f"recoveries {g['recoveries']}")
    rows = [{
        'phase': r['phase'],
        'kind': r['kind'],
        'seconds': f"{r['ended_at'] - r['started_at']:.2f}"
                   if r['ended_at'] is not None else '(open)',
        'pct': f"{100 * ((r['ended_at'] - r['started_at']) / wall):.1f}%"
               if r['ended_at'] is not None else '-',
        'detail': r['detail'],
    } for r in g['ledger']]
    _echo_table(rows, [('phase', 'PHASE'), ('kind', 'KIND'),
                       ('seconds', 'SECONDS'), ('pct', '%WALL'),
                       ('detail', 'DETAIL')])
    totals = [f"{k}={v:.1f}s" for k, v in (('goodput', g['goodput_s']),
                                           ('badput', g['badput_s']),
                                           ('overhead', g['overhead_s']))]
    click.echo('Totals: ' + '  '.join(totals))
    ck = g.get('ckpt')
    if ck:
        click.echo(f"Checkpointing: {ck['saves']} save(s) "
                   f"{ck['save_s']:.1f}s persisted / "
                   f"{ck['stall_s']:.1f}s step-loop stall, "
                   f"{ck['restores']} restore(s) {ck['restore_s']:.1f}s, "
                   f"last durable step {ck['last_step']}")


@jobs_group.command('cancel')
@click.argument('job_id', type=int)
@_clean_errors
def jobs_cancel(job_id):
    """Cancel a managed job."""
    from skypilot_tpu import jobs
    ok = jobs.cancel(job_id)
    click.echo('Cancellation requested.' if ok else 'Nothing to cancel.')


@jobs_group.command('logs')
@click.argument('job_id', type=int)
@click.option('--no-follow', is_flag=True, default=False)
@_clean_errors
def jobs_logs(job_id, no_follow):
    """Tail a managed job's logs."""
    from skypilot_tpu import jobs
    jobs.tail_logs(job_id, follow=not no_follow)


@cli.command('alerts')
@click.option('--history', is_flag=True, default=False,
              help='also list resolved alerts (newest first)')
@_clean_errors
def alerts_cmd(history):
    """Current SLO alerts from the API server's burn-rate evaluator
    (docs/operations.md §SLOs & alerting). Page-severity breaches
    freeze black-box incident bundles (`stpu debug bundles`)."""
    import requests as requests_lib

    from skypilot_tpu.client import sdk
    try:
        out = sdk.alerts(history=history)
    except requests_lib.RequestException as e:
        raise click.ClickException(
            f'API server unreachable at {sdk.server_url()} ({e}); '
            'start one with `stpu api start`') from e
    if not out.get('enabled'):
        click.echo('SLO evaluator is OFF (set SKYTPU_SLO=1 on the '
                   'API server).')
    rows = [{
        'rule': a.get('rule'),
        'sev': a.get('severity'),
        'target': a.get('target'),
        'state': a.get('state'),
        'value': (f"{a['value']:.1f} {a.get('op')} "
                  f"{a.get('threshold')}"
                  if isinstance(a.get('value'), (int, float)) else '-'),
        'burn': (f"{round((a.get('fast_frac') or 0) * 100)}%/"
                 f"{round((a.get('slow_frac') or 0) * 100)}%"),
        'since': _dt.datetime.fromtimestamp(
            a['fired_at'] or a['started_at']).strftime('%m-%d %H:%M:%S')
        if a.get('fired_at') or a.get('started_at') else '-',
    } for a in out.get('alerts', [])]
    _echo_table(rows, [('rule', 'RULE'), ('sev', 'SEV'),
                       ('target', 'TARGET'), ('state', 'STATE'),
                       ('value', 'VALUE'), ('burn', 'BURN F/S'),
                       ('since', 'SINCE')])
    if history:
        click.echo(click.style('Resolved:', bold=True))
        hrows = [{
            'rule': a.get('rule'),
            'sev': a.get('severity'),
            'target': a.get('target'),
            'fired': _dt.datetime.fromtimestamp(a['fired_at']).strftime(
                '%m-%d %H:%M:%S') if a.get('fired_at') else '-',
            'resolved': _dt.datetime.fromtimestamp(
                a['resolved_at']).strftime('%m-%d %H:%M:%S')
            if a.get('resolved_at') else '-',
            'paged': 'bundle' if a.get('paged') else '',
        } for a in out.get('history', [])]
        _echo_table(hrows, [('rule', 'RULE'), ('sev', 'SEV'),
                            ('target', 'TARGET'), ('fired', 'FIRED'),
                            ('resolved', 'RESOLVED'),
                            ('paged', 'CAPTURE')])


@cli.group('debug')
def debug_group():
    """Incident debugging: black-box flight-recorder bundles
    (docs/operations.md §Incident debugging)."""


def _echo_bundle_listing(out: dict) -> None:
    click.echo(f"Spool: {out.get('dir')} "
               f"(recorder {'on' if out.get('enabled', True) else 'OFF'})")
    rows = [{
        'file': b['file'],
        'when': _dt.datetime.fromtimestamp(b['ts']).strftime(
            '%m-%d %H:%M:%S') if b.get('ts') else '-',
        'proc': f"{b.get('proc')}[{b.get('pid')}]",
        'trigger': b.get('trigger'),
        'events': b.get('events'),
        'reason': (b.get('reason') or '')[:60],
    } for b in out.get('bundles', [])]
    _echo_table(rows, [('file', 'BUNDLE'), ('when', 'WHEN'),
                       ('proc', 'PROCESS'), ('trigger', 'TRIGGER'),
                       ('events', 'EVENTS'), ('reason', 'REASON')])
    dumps = out.get('sigquit_dumps') or []
    if dumps:
        click.echo(f'{len(dumps)} SIGQUIT stack dump(s): '
                   + ', '.join(d['file'] for d in dumps[:8]))


@debug_group.command('dump')
@click.argument('cluster')
@_clean_errors
def debug_dump(cluster):
    """Interrogate CLUSTER now: SIGQUIT every handler-registered
    framework process on its head (faulthandler thread stacks land in
    the bundle spool — no process is killed), then list the spool. The forensic first move
    on a hung or misbehaving cluster."""
    from skypilot_tpu import core
    out = core.debug_dump(cluster)
    signalled = out.get('signalled') or []
    click.echo(f'Signalled {len(signalled)} framework process(es) '
               f'on {cluster}.')
    _echo_bundle_listing(out)


@debug_group.command('bundles')
@click.argument('cluster', required=False)
@_clean_errors
def debug_bundles(cluster):
    """List committed incident bundles: CLUSTER's spool via its head
    agent, or the local/API-server host's spool when no cluster is
    named."""
    from skypilot_tpu import core
    _echo_bundle_listing(core.debug_bundles(cluster))


@cli.group('api')
def api_group():
    """API server management (analog of `sky api`)."""


@api_group.command('start')
@click.option('--port', type=int, default=46580)
@_clean_errors
def api_start(port):
    """Start the local API server daemon."""
    import os
    os.environ.setdefault('SKYTPU_API_SERVER_URL', f'http://127.0.0.1:{port}')
    from skypilot_tpu.client import sdk
    sdk.ensure_server()
    click.echo(f'API server healthy at {sdk.server_url()}')


@api_group.command('login')
@_clean_errors
def api_login():
    """Log in to the API server via its OAuth2/OIDC IdP (device flow).

    The server relays an RFC 8628 device authorization: open the
    printed URL, confirm the code, and the minted framework bearer
    token lands in ~/.skypilot_tpu/api_token (used automatically by
    every later CLI/SDK call; SKYTPU_API_TOKEN still overrides)."""
    import time as time_lib

    import requests as requests_lib

    from skypilot_tpu.client import sdk as sdk_lib
    url = sdk_lib.server_url()
    r = requests_lib.post(f'{url}/oauth/login/start', timeout=30)
    if r.status_code == 404:
        raise click.ClickException(
            'this API server has no OAuth IdP configured '
            '(SKYTPU_OAUTH_ISSUER); ask the operator for a token '
            'instead')
    if r.status_code != 200:
        raise click.ClickException(f'login start failed: {r.text[:300]}')
    flow = r.json()
    click.echo(f"Open {flow['verification_uri']}")
    click.echo(f"and confirm code: {flow['user_code']}")
    interval = max(int(flow.get('interval', 5)), 1)
    deadline = time_lib.time() + int(flow.get('expires_in', 600))
    while time_lib.time() < deadline:
        time_lib.sleep(interval)
        try:
            pr = requests_lib.post(f'{url}/oauth/login/poll',
                                   json={'handle': flow['handle']},
                                   timeout=30)
        except requests_lib.RequestException:
            continue  # transient network blip: keep polling (RFC 8628)
        if pr.status_code >= 500:
            continue  # proxy 502 / server restart: transient, retry
        if pr.status_code != 200:
            try:  # a proxy error may carry an HTML body, not JSON
                detail = pr.json().get('error', pr.text[:300])
            except ValueError:
                detail = pr.text[:300]
            raise click.ClickException(f'login failed: {detail}')
        body = pr.json()
        if body.get('pending'):
            if body.get('slow_down'):
                interval += 5
            continue
        path = sdk_lib.token_file_path()
        if os.path.dirname(path):  # bare filename: no dir to create
            os.makedirs(os.path.dirname(path), exist_ok=True)
        fd = os.open(path, os.O_WRONLY | os.O_CREAT | os.O_TRUNC, 0o600)
        with os.fdopen(fd, 'w', encoding='utf-8') as f:
            f.write(body['token'])
        click.echo(f"Logged in as {body['name']} (role "
                   f"{body['role']}); token saved to {path}")
        return
    raise click.ClickException('login timed out; run it again')


@api_group.command('info')
@_clean_errors
def api_info_cmd():
    """Show API server health."""
    from skypilot_tpu.client import sdk
    click.echo(sdk.api_info())


@api_group.command('requests')
@_clean_errors
def api_requests_cmd():
    """List recent API requests."""
    from skypilot_tpu.client import sdk
    _echo_table(sdk.api_requests(),
                [('request_id', 'ID'), ('name', 'NAME'),
                 ('status', 'STATUS')])


@cli.group('serve')
def serve_group():
    """Autoscaled serving (analog of `sky serve`)."""


@serve_group.command('up')
@click.argument('entrypoint', nargs=-1)
@click.option('--service-name', 'service_name', required=True,
              help='Service name (long-only: -n is the task name).')
@_common_task_options
@_clean_errors
def serve_up(entrypoint, service_name, name, workdir, cloud, accelerators,
             num_nodes, use_spot, envs, secrets):
    """Start an autoscaled service from a task YAML with a service: section."""
    from skypilot_tpu import serve
    task = _load_task(entrypoint, name, workdir, cloud, accelerators,
                      num_nodes, use_spot, envs, secrets)
    endpoint = serve.up(task, service_name)
    click.echo(f'Service {service_name} starting; endpoint: {endpoint}')


@serve_group.command('status')
@click.argument('service_name', required=False)
@_clean_errors
def serve_status(service_name):
    """Show services and their replicas."""
    from skypilot_tpu import serve
    for svc in serve.status(service_name):
        click.echo(f"{svc['name']}: {svc['status']} @ {svc['endpoint']}")
        for r in svc['replicas']:
            line = (f"  replica {r['replica_id']}: {r['status']} "
                    f"@ {r['endpoint']}")
            h = r.get('health') or {}
            eng = h.get('engine')
            if eng:
                # The LLM replica's live engine stats, compacted.
                line += (f"  [{eng.get('tokens_emitted', 0)} tok, "
                         f"{eng.get('active_slots', 0)}/"
                         f"{eng.get('slots', '?')} slots]")
            click.echo(line)


@serve_group.command('down')
@click.argument('service_name')
@_clean_errors
def serve_down(service_name):
    """Tear down a service."""
    from skypilot_tpu import serve
    serve.down(service_name)
    click.echo(f'Service {service_name} shutting down.')


@serve_group.command('logs')
@click.argument('service_name')
@click.argument('replica_id', type=int)
@click.option('--no-follow', is_flag=True, help='Print and exit.')
@_clean_errors
def serve_logs(service_name, replica_id, no_follow):
    """Tail a replica's logs (analog of `sky serve logs`)."""
    from skypilot_tpu import serve
    try:
        serve.tail_replica_logs(service_name, replica_id,
                                follow=not no_follow)
    except ValueError as e:
        raise click.ClickException(str(e)) from e


@serve_group.command('update')
@click.argument('entrypoint', nargs=-1)
@click.option('--service-name', 'service_name', required=True,
              help='Service name (long-only: -n is the task name).')
@_common_task_options
@_clean_errors
def serve_update(entrypoint, service_name, name, workdir, cloud,
                 accelerators, num_nodes, use_spot, envs, secrets):
    """Rolling-update a service to a new task version."""
    from skypilot_tpu import serve
    task = _load_task(entrypoint, name, workdir, cloud, accelerators,
                      num_nodes, use_spot, envs, secrets)
    try:
        version = serve.update(task, service_name)
    except ValueError as e:
        raise click.ClickException(str(e)) from e
    click.echo(f'Service {service_name} updating to v{version} '
               '(rolling).')


@cli.group('local')
def local_group():
    """Local dev cluster via kind (analog of `sky local up`)."""


@local_group.command('up')
@click.option('--name', default=None, help='kind cluster name.')
@_clean_errors
def local_up_cmd(name):
    """Create a local kind cluster and register it as capacity."""
    from skypilot_tpu import local_cluster
    ctx = local_cluster.local_up(name or local_cluster.DEFAULT_NAME)
    click.echo(f'Local cluster up. Kubeconfig context: {ctx}\n'
               f'Launch onto it with: stpu launch --cloud kubernetes '
               f'-- <cmd>   (region {ctx})')


@local_group.command('down')
@click.option('--name', default=None, help='kind cluster name.')
@_clean_errors
def local_down_cmd(name):
    """Tear the local kind cluster down."""
    from skypilot_tpu import local_cluster
    existed = local_cluster.local_down(name or local_cluster.DEFAULT_NAME)
    click.echo('Local cluster deleted.' if existed
               else 'No local cluster found.')


@cli.group('storage')
def storage_group():
    """Object-store buckets (reference: `sky storage`)."""


def _store_for_read(uri):
    """(store, names, exact_rel): the store + object names for a URI.
    Prefix URIs list children; a URI naming an EXACT object falls back to
    listing its parent prefix (the stores' prefix-stripping would
    otherwise drop the exact-match key and report the object missing)."""
    from skypilot_tpu.data import storage as storage_lib
    store = storage_lib.Storage.from_config(uri).store()
    names = store.list_objects()
    if names:
        return store, names, ''
    scheme, bucket, prefix = storage_lib.parse_source(uri)
    if not prefix:
        return store, [], ''
    parent, _, leaf = prefix.rpartition('/')
    parent_uri = f'{scheme}://{bucket}' + (f'/{parent}' if parent else '')
    parent_store = storage_lib.Storage.from_config(parent_uri).store()
    if leaf in parent_store.list_objects():
        return parent_store, [leaf], leaf
    return store, [], ''


@storage_group.command('ls')
@click.argument('uri')
@_clean_errors
def storage_ls(uri):
    """List objects under a bucket URI (gs:// s3:// az:// oci:// cos://
    file://); an exact-object URI lists that object."""
    store, names, _ = _store_for_read(uri)
    if not names:
        click.echo(f'{uri}: empty (or missing)')
        return
    for name in names:
        click.echo(name)
    click.echo(f'-- {len(names)} object(s) in {store.url}')


@storage_group.command('delete')
@click.argument('uri')
@click.option('--yes', '-y', is_flag=True, help='Skip confirmation.')
@_clean_errors
def storage_delete(uri, yes):
    """Delete every object under a bucket URI (prefix granularity)."""
    from skypilot_tpu.data import storage as storage_lib
    store = storage_lib.Storage.from_config(uri).store()
    if not yes:
        click.confirm(f'Delete ALL objects under {store.url}?', abort=True)
    store.delete()
    click.echo(f'Deleted {store.url}.')


@storage_group.command('cp')
@click.argument('src')
@click.argument('dst')
@_clean_errors
def storage_cp(src, dst):
    """Copy between a local path and a bucket URI (either direction), or
    bucket-to-bucket across providers."""
    from skypilot_tpu.data import storage as storage_lib
    src_is_uri = '://' in src
    dst_is_uri = '://' in dst
    if src_is_uri and dst_is_uri:
        from skypilot_tpu.data import data_transfer
        n = data_transfer.transfer(src, dst)
        click.echo(f'Copied {n} object(s) {src} -> {dst}.')
    elif src_is_uri:
        store, names, exact = _store_for_read(src)
        if not names:
            raise click.ClickException(f'{src}: no such object or prefix')
        if exact:
            store.download(dst, src_rel=exact)
        else:
            store.download(dst)
        click.echo(f'Downloaded {src} -> {dst}.')
    elif dst_is_uri:
        storage_lib.Storage.from_config(dst).store().upload(src)
        click.echo(f'Uploaded {src} -> {dst}.')
    else:
        raise click.UsageError('At least one side must be a bucket URI.')


@cli.group('ckpt')
def ckpt_group():
    """Inspect native checkpoint directories (skypilot_tpu/ckpt/
    format: checksummed shard+manifest step dirs with commit markers).
    Works on any local path or mounted bucket dir — no server, no jax."""


def _ckpt_rows(directory):
    from skypilot_tpu.ckpt import manifest as manifest_lib
    rows = []
    for step, path in manifest_lib.committed_steps(directory):
        rows.append((step, path, True))
    for path in manifest_lib.partial_dirs(directory):
        name = os.path.basename(path)
        if name.endswith(manifest_lib.TMP_SUFFIX):
            name = name[:-len(manifest_lib.TMP_SUFFIX)]
        step = manifest_lib.parse_step_dirname(name)
        rows.append((step if step is not None else -1, path, False))
    return sorted(rows)


@ckpt_group.command('ls')
@click.argument('directory', type=click.Path(exists=True, file_okay=False))
@_clean_errors
def ckpt_ls(directory):
    """List checkpoint steps: committed ones plus torn-write debris
    (uncommitted/.tmp dirs a crash or partial mirror upload left)."""
    import time as time_lib

    from skypilot_tpu.ckpt import manifest as manifest_lib
    rows = []
    for step, path, committed in _ckpt_rows(directory):
        row = {'step': step, 'state': 'committed' if committed
               else 'PARTIAL', 'hosts': '-', 'arrays': '-', 'mb': '-',
               'age': '-'}
        if committed:
            report = manifest_lib.verify_step(path, deep=False)
            row.update(hosts=report['hosts'], arrays=report['arrays'],
                       mb=f"{report['nbytes'] / 1e6:.1f}")
            if not report['ok']:
                # Shallow validation (manifests + shard sizes) already
                # failed: restore would skip this step — say so here,
                # not only in `ckpt verify`.
                row['state'] = 'CORRUPT'
            else:
                try:
                    top = manifest_lib.read_manifest(path)
                    row['age'] = \
                        f"{int(time_lib.time() - top.get('ts', 0))}s"
                except manifest_lib.CheckpointError:
                    row['state'] = 'CORRUPT'
        rows.append(row)
    _echo_table(rows, [('step', 'STEP'), ('state', 'STATE'),
                       ('hosts', 'HOSTS'), ('arrays', 'ARRAYS'),
                       ('mb', 'MB'), ('age', 'AGE')])


@ckpt_group.command('verify')
@click.argument('directory', type=click.Path(exists=True, file_okay=False))
@click.option('--step', type=int, default=None,
              help='Verify one step only (default: every committed step).')
@click.option('--deep/--shallow', 'deep', default=True,
              help='--deep (default) re-reads every array\'s byte range '
                   'and verifies its crc32 through the same parallel '
                   'range-reader restore uses; --shallow stops at '
                   'manifest + shard-size checks.')
@click.option('--readers', type=int, default=None,
              help='Range-reader pool size for --deep '
                   '(default: SKYTPU_CKPT_READERS, 8).')
@_clean_errors
def ckpt_verify(directory, step, deep, readers):
    """Checksum-verify committed steps — the same validation restore
    runs. Exit 1 if any verified step is corrupt (restore would skip it
    and fall back to the previous durable step)."""
    from skypilot_tpu.ckpt import manifest as manifest_lib
    targets = [(s, p) for s, p in manifest_lib.committed_steps(directory)
               if step is None or s == step]
    if not targets:
        raise click.ClickException(
            f'no committed step{f" {step}" if step is not None else "s"} '
            f'under {directory}')
    bad = 0
    for s, path in targets:
        report = manifest_lib.verify_step(path, deep=deep, readers=readers)
        if report['ok']:
            click.echo(f"step {s}: OK ({report['hosts']} host(s), "
                       f"{report['arrays']} arrays, "
                       f"{report['nbytes'] / 1e6:.1f} MB)")
        else:
            bad += 1
            click.echo(click.style(
                f"step {s}: CORRUPT — {'; '.join(report['errors'])}",
                fg='red'))
    partials = manifest_lib.partial_dirs(directory)
    if partials:
        click.echo(f'{len(partials)} partial dir(s) (torn writes, '
                   f'invisible to restore): '
                   + ', '.join(os.path.basename(p) for p in partials))
    if bad:
        sys.exit(1)


@cli.group('volumes')
def volumes_group():
    """Persistent volumes (reference: `sky volumes`)."""


@volumes_group.command('create')
@click.argument('name')
@click.option('--size', default=100, help='Size in GB.')
@click.option('--cloud', default='local')
@click.option('--region', default=None,
              help='GCP region / kubeconfig context for k8s PVCs.')
@click.option('--zone', default=None)
@click.option('--type', 'volume_type', default='pd-balanced',
              help='GCP disk type / k8s StorageClass name.')
@click.option('--access-mode', default='ReadWriteOnce', show_default=True,
              help='k8s PVC access mode (ReadWriteMany for multi-pod '
                   'clusters, if the StorageClass supports it).')
@_clean_errors
def volumes_create(name, size, cloud, region, zone, volume_type,
                   access_mode):
    from skypilot_tpu import volumes as volumes_lib
    vol = volumes_lib.create(name, size_gb=size, cloud=cloud,
                             region=region, zone=zone,
                             volume_type=volume_type,
                             access_mode=access_mode)
    click.echo(f'Created volume {vol["name"]} ({vol["size_gb"]} GB, '
               f'{vol["cloud"]}).')


@volumes_group.command('ls')
def volumes_ls():
    from skypilot_tpu import volumes as volumes_lib
    vols = volumes_lib.list_volumes()
    if not vols:
        click.echo('No volumes.')
        return
    for v in vols:
        mode = v.get('access_mode') or 'ReadWriteOnce'
        click.echo(f'{v["name"]:24s} {v["cloud"]:8s} {v["size_gb"]:>6d}GB '
                   f'{v["status"]:8s} {mode:14s} '
                   f'attached={v["attached_to"] or "-"}')


@volumes_group.command('rm')
@click.argument('name')
def volumes_rm(name):
    from skypilot_tpu import volumes as volumes_lib
    volumes_lib.delete(name)
    click.echo(f'Deleted volume {name}.')


@cli.group('users')
def users_group():
    """User/RBAC management for the API server (reference: `sky/users`)."""


@users_group.command('add')
@click.argument('name')
@click.option('--token', required=True, help='Bearer token for this user.')
@click.option('--role', default='user',
              type=click.Choice(['viewer', 'user', 'admin']))
def users_add(name, token, role):
    from skypilot_tpu import users as users_lib
    users_lib.add_user(name, token, role)
    click.echo(f'Added user {name} ({role}).')


@users_group.command('ls')
def users_ls():
    from skypilot_tpu import users as users_lib
    rows = users_lib.list_users()
    if not rows:
        click.echo('No users registered (single-user mode).')
        return
    for u in rows:
        click.echo(f'{u["name"]:24s} {u["role"]}')


@users_group.command('rm')
@click.argument('name')
def users_rm(name):
    from skypilot_tpu import users as users_lib
    users_lib.remove_user(name)
    click.echo(f'Removed user {name}.')


@cli.group('workspaces')
def workspaces_group():
    """Workspace management (reference: `sky/workspaces` grouping)."""


@workspaces_group.command('ls')
def workspaces_ls():
    from skypilot_tpu import workspaces as workspaces_lib
    for w in workspaces_lib.list_workspaces():
        marker = '*' if w['active'] else ' '
        click.echo(f'{marker} {w["name"]:24s} clusters={w["clusters"]}')


@workspaces_group.command('create')
@click.argument('name')
@_clean_errors
def workspaces_create(name):
    from skypilot_tpu import workspaces as workspaces_lib
    workspaces_lib.create(name)
    click.echo(f'Created workspace {name}.')


@workspaces_group.command('switch')
@click.argument('name')
@_clean_errors
def workspaces_switch(name):
    from skypilot_tpu import workspaces as workspaces_lib
    workspaces_lib.switch(name)
    click.echo(f'Active workspace: {name}.')


@workspaces_group.command('rm')
@click.argument('name')
@_clean_errors
def workspaces_rm(name):
    from skypilot_tpu import workspaces as workspaces_lib
    workspaces_lib.delete(name)
    click.echo(f'Removed workspace {name}.')


if __name__ == '__main__':
    cli()
