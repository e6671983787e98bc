"""Post-provision node bootstrap: wait for SSH, install the runtime, start
the cluster daemon.

Reference analog: ``sky/provision/instance_setup.py`` (``:292-490`` — runtime
install over parallel SSH, head/worker daemon start) and
``sky/backends/wheel_utils.py`` (the client's own code is shipped to the
cluster so remote runtime == client version). TPU-native differences: no Ray
to start and no wheel build — the pure-python package tree is rsynced as-is
and run with the system python3 (TPU VM images ship one); the gang substrate
is the C++ ``gangd`` / python driver, which runs from that tree.
"""
from __future__ import annotations

import concurrent.futures as cf
import os
import shlex
import time
from typing import Optional, Sequence

from skypilot_tpu import exceptions
from skypilot_tpu.provision import common
from skypilot_tpu.utils.command_runner import CommandRunner

# Where the framework lives on every worker (HOME-relative).
REMOTE_RUNTIME_DIR = '~/.skytpu/runtime'
REMOTE_WORKDIR = '~/sky_workdir'
# Base of the persistent XLA compilation cache tree on every worker.
# Replicas get a per-model-version subdir (serve/replica_managers.py
# injects SKYTPU_COMPILE_CACHE=<base>/<service>-v<version>) so a
# replacement replica deserializes its predecessors' lowered programs
# instead of recompiling them (utils/jax_env.enable_compile_cache).
REMOTE_COMPILE_CACHE_DIR = '~/.skytpu/compile_cache'


def _package_root() -> str:
    """Directory containing the ``skypilot_tpu`` package (synced to nodes)."""
    import skypilot_tpu
    return os.path.dirname(os.path.dirname(
        os.path.abspath(skypilot_tpu.__file__)))


def wait_for_ssh(runners: Sequence[CommandRunner], timeout: float = 300.0,
                 poll: float = 5.0) -> None:
    """Block until every worker answers a trivial command (reference:
    ``provisioner.wait_for_ssh :387``). Parallel across workers."""
    deadline = time.time() + timeout

    def _wait_one(runner: CommandRunner) -> None:
        while True:
            if runner.run('true') == 0:
                return
            if time.time() > deadline:
                raise exceptions.ClusterNotUpError(
                    f'Worker {getattr(runner, "ip", "?")} unreachable over '
                    f'SSH after {timeout:.0f}s')
            time.sleep(poll)

    with cf.ThreadPoolExecutor(max_workers=min(32, len(runners))) as pool:
        list(pool.map(_wait_one, runners))


def install_runtime(runners: Sequence[CommandRunner],
                    python: str = 'python3') -> None:
    """Ship the framework to every worker and verify the worker's python can
    import it (the wheel-upload analog, ``wheel_utils.py:1-60``).

    ``python`` is the interpreter on the WORKER (TPU VM images ship the ML
    stack on the system python3); tests point it at their own venv."""
    src = os.path.join(_package_root(), 'skypilot_tpu')

    def _install_one(runner: CommandRunner) -> None:
        runner.run(f'mkdir -p {REMOTE_RUNTIME_DIR} {REMOTE_WORKDIR}')
        runner.rsync(src, f'{REMOTE_RUNTIME_DIR}/skypilot_tpu', up=True)
        rc = runner.run(
            f'PYTHONPATH={REMOTE_RUNTIME_DIR} {shlex.quote(python)} -c '
            + shlex.quote('import skypilot_tpu.agent.job_lib'))
        if rc != 0:
            raise exceptions.ClusterNotUpError(
                f'Runtime install failed on {getattr(runner, "ip", "?")}: '
                f'{python} cannot import the synced skypilot_tpu package')

    with cf.ThreadPoolExecutor(max_workers=min(32, len(runners))) as pool:
        list(pool.map(_install_one, runners))


# Python deps the on-pod agent runtime needs beyond the stdlib. Slim pod
# images (the GKE default) ship none of them; bootstrap installs them
# rather than walling the user off behind "bring your own image"
# (COVERAGE gap #3 — the reference requires its wheel's deps in the pod
# image; we degrade gracefully instead).
AGENT_RUNTIME_DEPS = ('grpcio', 'protobuf', 'requests', 'PyYAML',
                      'filelock')


def ensure_runtime_deps(runners: Sequence[CommandRunner],
                        python: str = 'python3') -> None:
    """Install the agent's python deps on workers whose image lacks them.
    Probe first (no-op on full images), then pip install --user; a pod
    with neither deps nor pip fails with an actionable message instead of
    the opaque agent-never-listened error."""
    probe = (f'{shlex.quote(python)} -c '
             + shlex.quote('import grpc, google.protobuf, requests, yaml, '
                           'filelock'))
    pip_install = (f'{shlex.quote(python)} -m pip install --user '
                   + ' '.join(AGENT_RUNTIME_DEPS))

    def _ensure_one(idx_runner) -> None:
        idx, runner = idx_runner
        if runner.run(probe) == 0:
            return
        if runner.run(pip_install) != 0:
            raise exceptions.ClusterNotUpError(
                f'Worker {idx}: agent runtime deps missing and pip '
                f'install failed — use an image with '
                f'{", ".join(AGENT_RUNTIME_DEPS)} preinstalled '
                '(set `image_id:` on the task). For air-gapped '
                'clusters, build one from docker/Dockerfile.k8s-worker '
                '(see docs/clouds.md).')
        if runner.run(probe) != 0:
            raise exceptions.ClusterNotUpError(
                f'Worker {idx}: agent runtime deps still unimportable '
                'after pip install.')

    with cf.ThreadPoolExecutor(max_workers=min(32, len(runners))) as pool:
        list(pool.map(_ensure_one, enumerate(runners)))


def push_cluster_key_to_head(head_runner: CommandRunner,
                             key_path: str) -> None:
    """Install the cluster SSH private key on the head so the head-side
    gang driver can fan out to peer workers (driver-on-head; reference: the
    cluster YAML's auth key is uploaded so Ray head reaches workers,
    ``backends/backend_utils.py:643`` ssh_private_key plumbing). Staged
    through a directory rsync — runners sync dirs, and the key must never
    appear on a command line."""
    import shutil
    import tempfile

    with tempfile.TemporaryDirectory(prefix='skytpu-key-') as td:
        shutil.copy(os.path.expanduser(key_path),
                    os.path.join(td, 'cluster_key'))
        head_runner.rsync(td, f'{REMOTE_RUNTIME_DIR}/keys', up=True)
    head_runner.run(f'chmod 700 {REMOTE_RUNTIME_DIR}/keys && '
                    f'chmod 600 {REMOTE_RUNTIME_DIR}/keys/cluster_key')


def _agent_start_cmd(pidfile: str, cluster_dir: str, flags: str,
                     python: str) -> str:
    """The one pidfile-guarded nohup launch template for agents (head and
    worker variants differ only in pidfile and flags)."""
    return (
        f'if [ -f {pidfile} ] && kill -0 $(cat {pidfile}) 2>/dev/null; then '
        f'true; else '
        f'mkdir -p {cluster_dir} && '
        f'PYTHONPATH={REMOTE_RUNTIME_DIR} nohup {shlex.quote(python)} -m '
        f'skypilot_tpu.agent.rpc_server --cluster-dir {cluster_dir} '
        f'{flags} >/dev/null 2>&1 & echo $! > {pidfile}; fi')


def start_agent_on_head(head_runner: CommandRunner, cluster_name: str,
                        python: str = 'python3') -> None:
    """Start the on-cluster agent (skylet analog: the gRPC server over the
    head's job table/logs, ``agent/rpc_server.py``) detached on the head
    (reference: ``start_skylet_on_head_node :490``). The server picks a
    free port (heads can be shared hosts — local controller clusters) and
    records it in ``agent.port`` inside the cluster dir; clients read that
    file over SSH before dialing through the tunnel. Idempotent: a second
    start finds the pidfile's process alive and exits."""
    pidfile = f'{REMOTE_RUNTIME_DIR}/daemon-{cluster_name}.pid'
    cluster_dir = f'{REMOTE_RUNTIME_DIR}/clusters/{cluster_name}'
    rc = head_runner.run(_agent_start_cmd(
        pidfile, cluster_dir,
        f'--port 0 --port-file {cluster_dir}/agent.port', python))
    if rc != 0:
        raise exceptions.ClusterNotUpError(
            f'Starting the cluster agent on the head failed (rc={rc})')


def agent_token_path(cluster_name: str) -> str:
    """Where the shared agent auth token lives on every node (head reads
    it to authenticate to worker agents; workers enforce it)."""
    return f'{REMOTE_RUNTIME_DIR}/clusters/{cluster_name}/token/agent.token'


def push_agent_token(runners: Sequence[CommandRunner],
                     cluster_name: str) -> None:
    """Install the cluster's shared agent token on every node, over the
    same authenticated channel as the cluster SSH key. Non-loopback
    worker agents reject RPCs without it (the streaming Exec RPC is
    arbitrary command execution — it must not be reachable by any peer
    with mere pod-network connectivity). Staged through a DEDICATED
    ``token/`` subdir (like the key push's ``keys/``): runners rsync whole
    directories with mirror semantics, so syncing onto the live cluster
    dir would wipe the head agent's port file and job table.

    GENERATE-IF-ABSENT (r3 advisor medium): agent starts are
    pidfile-guarded no-ops when an agent is already alive, and running
    agents hold their token in memory — so re-provisioning a cluster
    whose agents survived (interrupted launch, stale record) must push
    the token those agents already enforce, not mint a fresh one that
    would wedge every subsequent Exec RPC with UNAUTHENTICATED."""
    import secrets
    import tempfile

    token = None
    rc, existing = runners[0].output(
        f'cat {agent_token_path(cluster_name)} 2>/dev/null')
    if rc == 0 and existing.strip():
        token = existing.strip()
    if token is None:
        token = secrets.token_hex(32)
    token_dir = f'{REMOTE_RUNTIME_DIR}/clusters/{cluster_name}/token'
    with tempfile.TemporaryDirectory(prefix='skytpu-token-') as td:
        path = os.path.join(td, 'agent.token')
        with open(path, 'w', encoding='utf-8') as f:
            f.write(token)
        os.chmod(path, 0o600)
        for runner in runners:
            runner.rsync(td, token_dir, up=True)
            runner.run(f'chmod 700 {token_dir} && '
                       f'chmod 600 {agent_token_path(cluster_name)}')


def start_worker_agents(runners: Sequence[CommandRunner], cluster_name: str,
                        port: int, python: str = 'python3') -> None:
    """Start an agent on EVERY worker at a fixed port (pods have unique
    IPs, so one well-known port works). This is the gang driver's peer
    transport where no sshd exists: the head-side driver reaches workers
    through their agents' Exec RPC (``agent/exec_relay.py``). The agents
    require the bootstrap-pushed token (``push_agent_token``) on every
    RPC — without it a non-loopback agent would hand arbitrary command
    execution to the whole pod network."""

    def _start_one(idx_runner) -> None:
        idx, runner = idx_runner
        pidfile = f'{REMOTE_RUNTIME_DIR}/agent-{cluster_name}-w{idx}.pid'
        cluster_dir = f'{REMOTE_RUNTIME_DIR}/clusters/{cluster_name}'
        rc = runner.run(_agent_start_cmd(
            pidfile, cluster_dir,
            f'--port {port} --host 0.0.0.0 '
            f'--token-file {cluster_dir}/token/agent.token', python))
        if rc != 0:
            raise exceptions.ClusterNotUpError(
                f'Starting the worker agent failed on worker {idx} '
                f'(rc={rc})')
        # Liveness: nohup always exits 0, so an agent that dies at once
        # (missing grpcio in the pod image, port taken) would otherwise
        # surface only as opaque exec-relay errors at first job run.
        probe = (f'{shlex.quote(python)} -c "import socket, time\n'
                 'import sys\n'
                 'for _ in range(30):\n'
                 '    try:\n'
                 f'        socket.create_connection((\'127.0.0.1\', {port}),'
                 ' 1).close()\n'
                 '        sys.exit(0)\n'
                 '    except OSError:\n'
                 '        time.sleep(0.5)\n'
                 'sys.exit(1)"')
        if runner.run(probe) != 0:
            raise exceptions.ClusterNotUpError(
                f'Worker agent on worker {idx} never started listening on '
                f'port {port} — does the node image carry the runtime '
                'deps (grpcio, protobuf)?')

    with cf.ThreadPoolExecutor(max_workers=min(32, len(runners))) as pool:
        list(pool.map(_start_one, enumerate(runners)))


def provision_compile_cache(runners: Sequence[CommandRunner],
                            cache_dir: str,
                            seed_dir: Optional[str] = None) -> None:
    """Provision the persistent XLA compile-cache dir on every worker
    (parallel, idempotent), optionally pre-seeding it from a bucket
    mirror so a replica on a FRESH node still boots warm.

    ``cache_dir`` is the per-model-version leaf (what the replica's
    SKYTPU_COMPILE_CACHE will point at). ``seed_dir`` is a bucket-mounted
    snapshot of a predecessor's cache (conventionally
    ``<ckpt_bucket>/compile_cache/<key>``, next to the ckpt mirror);
    ``cp -n`` pulls only entries the local dir lacks, so a re-bootstrap
    never clobbers newer locally-written entries. Best-effort by design:
    the cache accelerates boots, it never gates them — the engine
    mkdirs the leaf itself and degrades to a cold compile on any
    failure here."""

    def _provision_one(runner: CommandRunner) -> None:
        runner.run(f'mkdir -p {shlex.quote(cache_dir)}')
        if seed_dir:
            # -n: never overwrite; 2>/dev/null: an empty/absent seed is
            # the normal first-deploy case, not an error.
            runner.run(f'cp -rn {shlex.quote(seed_dir)}/. '
                       f'{shlex.quote(cache_dir)}/ 2>/dev/null || true')

    try:
        with cf.ThreadPoolExecutor(max_workers=min(32, len(runners))) as pool:
            list(pool.map(_provision_one, runners))
    except Exception as exc:  # noqa: BLE001 — cache is an accelerator
        print(f'[bootstrap] compile-cache provisioning skipped: {exc}')


def bootstrap_cluster(cluster_name: str, info: common.ClusterInfo,
                      runners: Sequence[CommandRunner],
                      ssh_timeout: float = 300.0,
                      start_daemon: bool = True,
                      python: str = 'python3',
                      worker_agents_port: Optional[int] = None,
                      compile_cache_dir: Optional[str] = None,
                      compile_cache_seed: Optional[str] = None) -> None:
    """Full post-provision setup for a freshly created cluster: SSH
    reachability -> runtime install on every worker -> head daemon (and,
    for agent-exec clusters like GKE, an agent on every worker). When
    ``compile_cache_dir`` is set (serve replicas), the persistent XLA
    compile-cache tree is provisioned (and bucket-seeded) too."""
    if not runners:
        return
    wait_for_ssh(runners, timeout=ssh_timeout)
    install_runtime(runners, python=python)
    if compile_cache_dir:
        provision_compile_cache(runners, compile_cache_dir,
                                seed_dir=compile_cache_seed)
    if worker_agents_port is not None:
        # Pod-network clusters run agents on EVERY node; slim images may
        # lack the agent deps — install them before any agent starts.
        ensure_runtime_deps(runners, python=python)
    if start_daemon:
        from skypilot_tpu import authentication
        key_path, _ = authentication.get_or_create_ssh_keypair()
        push_cluster_key_to_head(runners[0], key_path)
        start_agent_on_head(runners[0], cluster_name, python=python)
        if worker_agents_port is not None and len(runners) > 1:
            # Token to ALL nodes (the head-side driver reads it to dial
            # the workers), then start the enforcing worker agents.
            push_agent_token(runners, cluster_name)
            start_worker_agents(runners[1:], cluster_name,
                                worker_agents_port, python=python)
    # Optional external log shipping (logs.store in config; reference:
    # provisioner.py:714-722 installing fluentbit at provision time).
    # Genuinely best-effort here: a config typo surfaced at launch entry
    # (execution.launch validates) and must not strand a half-bootstrapped
    # cluster this late.
    try:
        from skypilot_tpu import logs as logs_lib
        agent = logs_lib.agent_from_config()
        if agent is not None:
            cmd = agent.install_command(cluster_name)
            for runner in runners:
                runner.run(cmd)
    except Exception as exc:  # noqa: BLE001 — shipping is auxiliary
        print(f'[bootstrap] log shipping skipped: {exc}')
