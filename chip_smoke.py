#!/usr/bin/env python3
"""chip_smoke.py: the train step and the serving replica, started, run
and stopped on the TPU through their own entry points, at the full width
of ``bench-1b``. The quickest proof that the system still starts on the
chip; no speed is claimed from it.

    python chip_smoke.py                 # on a machine with a TPU
    python chip_smoke.py --rehearse-cpu  # same phases, tiny model, CPU

A chip belongs to one process at a time, so this parent never
initialises a JAX backend: every phase is a child process, started
through the module entry point a user would type, in its own process
group, killed at its timeout. Phases, in order (each prints one JSON
line; any phase not ``ok`` makes the exit code non-zero):

  devices       python -m skypilot_tpu.utils.jax_env — what JAX finds.
                Anything but a TPU ends the run here (unless rehearsing).
  train-1chip   python -m skypilot_tpu.train.run, six steps.
  serve         python -m skypilot_tpu.serve.llm_server with today's
                defaults, driven by python -m skypilot_tpu.serve.loadgen,
                one greedy request three times (past the first the prefix
                trie serves its prompt), plus a shared-prefix hit; decode
                through paged_decode (which writes the step's K/V row
                too); SIGTERM -> drain -> exit 0.
  kernels       each Pallas kernel compiled (not interpreted) against its
                jnp reference (paged_decode, mla_decode and kda_step at the
                benchmark cells' geometries) and the train step's HLO searched
                for the Mosaic call.
  launch-local  execution.launch(Task(run='python -m ...train.run'),
                cloud='local'): the orchestrator's own path.
  train-4chip, serve-tp4   with four or more devices; else a stated skip.

The last line of stdout on success is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Children's full logs land in ``chiprun_out/chip_smoke/``.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import signal
import socket
import statistics
import subprocess
import sys
import tempfile
import time
import urllib.error
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
LOG_DIR = os.path.join(ROOT, 'chiprun_out', 'chip_smoke')
FINGERPRINT = f'chip-smoke-{os.getpid()}'
# The whole run must end inside the driver's 1200 s; leave room to stop.
BUDGET_S = 1140.0
T0 = time.monotonic()

# What ops/attention.py logs when a Pallas kernel gives way to its jnp
# reference on a shape it cannot take: never acceptable at these sizes.
FALLBACK_TAG = '[kernel-fallback]'

REAL = dict(model='bench-1b', tp_model='bench-1b', vocab=32768,
            seq=2048, batch=4, prompt=128, new=32, head=64,
            flash_seqs=(2048, 4096), hq=16, hkv=8, d=128,
            # The benchmark cells' pool: 48 slots, 2,049 blocks of 16,
            # max_len 2048, 16/8 heads x 128, bf16.
            paged=dict(slots=48, blocks=2049, block=16, max_blocks=128,
                       hq=16, hkv=8, d=128),
            # xing-docs-sessions' latent pool: 8,193 blocks of 16,
            # max_len 4096, 32 heads over one 512 + 64 row, bf16.
            mla=dict(slots=48, blocks=8193, block=16, max_blocks=256),
            # kimi-linear-docs-steady's state: four KDA layers x 48
            # slots x 32 heads of [128, 128] float32.
            kda=dict(layers=4, slots=48, heads=32, d=128))
# tiny-mh: 8 kv heads, so --tp 4 divides them. The interpreter cannot
# afford the kernels' real VMEM caps, so the rehearsal names small ones.
REHEARSAL = dict(model='tiny', tp_model='tiny-mh', vocab=256,
                 seq=128, batch=2, prompt=16, new=8, head=32,
                 flash_seqs=(128, 256), flash_cap_seq=256,
                 hq=4, hkv=2, d=64,
                 paged=dict(slots=4, blocks=33, block=16, max_blocks=8,
                            hq=4, hkv=2, d=128),
                 mla=dict(slots=4, blocks=33, block=16, max_blocks=8,
                          heads=4, rank=96, rope=16),
                 kda=dict(layers=2, slots=4, heads=2, d=128))


def remaining() -> float:
    return BUDGET_S - (time.monotonic() - T0)


# -- children ---------------------------------------------------------------

_live: list = []  # Popen objects whose process groups may still run


def child_env(rehearse: bool, **extra) -> dict:
    env = dict(os.environ)
    env['PYTHONPATH'] = ROOT + os.pathsep + env.get('PYTHONPATH', '')
    env['PYTHONUNBUFFERED'] = '1'
    # Daemons the launcher detaches carry this, so cleanup can find them.
    env['SKYTPU_SESSION_FINGERPRINT'] = FINGERPRINT
    if rehearse:
        env['JAX_PLATFORMS'] = 'cpu'
        env['XLA_FLAGS'] = '--xla_force_host_platform_device_count=4'
    env.update(extra)
    return env


def spawn(argv, env, log_path) -> subprocess.Popen:
    log = open(log_path, 'wb')
    proc = subprocess.Popen(argv, cwd=ROOT, env=env, stdout=log,
                            stderr=subprocess.STDOUT,
                            start_new_session=True)
    log.close()
    _live.append(proc)
    return proc


def kill_group(proc: subprocess.Popen) -> None:
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except (ProcessLookupError, PermissionError):
        pass
    try:
        proc.wait(timeout=10)
    except subprocess.TimeoutExpired:
        pass


def run_child(name, argv, env, timeout_s):
    """Run a child to its end or its timeout; (rc, text). rc None means
    it was killed at the timeout."""
    log_path = os.path.join(LOG_DIR, f'{name}.log')
    proc = spawn(argv, env, log_path)
    try:
        rc = proc.wait(timeout=max(min(timeout_s, remaining()), 1.0))
    except subprocess.TimeoutExpired:
        rc = None
    kill_group(proc)  # stragglers in the group, either way
    with open(log_path, encoding='utf-8', errors='replace') as f:
        return rc, f.read()


def cleanup() -> None:
    for proc in _live:
        if proc.poll() is None:
            kill_group(proc)
    try:  # daemons the launcher detached into their own sessions
        from skypilot_tpu.utils import tpu_doctor
        tpu_doctor.reap_stray_processes(own_fingerprint=FINGERPRINT)
    except ImportError:
        pass  # no repo beside this script: no child ever started


def _on_signal(signum, frame):
    del frame
    cleanup()
    sys.exit(128 + signum)


# -- parsing what the entry points print -----------------------------------


def prefixed_json(text: str, prefix: str) -> list:
    return [json.loads(line[len(prefix):]) for line in text.splitlines()
            if line.startswith(prefix)]


def device_lines(text: str) -> list:
    return prefixed_json(text, '[device] ')


def train_steps(text: str) -> list:
    """[(loss, step_seconds)] from '[train] step i/n loss=… step_s=…'."""
    out = []
    for line in text.splitlines():
        if line.startswith('[train] step ') and 'loss=' in line:
            fields = dict(f.split('=') for f in line.split()[3:])
            out.append((float(fields['loss']), float(fields['step_s'])))
    return out


def balanced(in_use) -> bool:
    """Every device's bytes_in_use within 2x of the others: nothing
    piled on chip 0."""
    return bool(in_use) and all(in_use) and max(in_use) <= 2 * min(in_use)


# -- HTTP against the replica ----------------------------------------------


def http_json(url: str, body=None, timeout: float = 120.0):
    """(status, parsed body) — GET, or POST when ``body`` is given."""
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, headers={'Content-Type': 'application/json'})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, {'error': e.read().decode('utf-8', 'replace')[:300]}


def free_port() -> int:
    with socket.socket() as s:
        s.bind(('127.0.0.1', 0))
        return s.getsockname()[1]


def prompt_tokens(seed: int, n: int, vocab: int) -> list:
    return [(seed * 7919 + i * 104729) % (vocab - 1) + 1 for i in range(n)]


class Replica:
    """One llm_server child: started, waited for, stopped."""

    def __init__(self, name, args, env, ready_timeout_s):
        self.name = name
        self.port = free_port()
        self.url = f'http://127.0.0.1:{self.port}'
        self.log_path = os.path.join(LOG_DIR, f'{name}.log')
        self.ready_s = None
        t0 = time.monotonic()
        self.proc = spawn(
            [sys.executable, '-m', 'skypilot_tpu.serve.llm_server',
             '--port', str(self.port), '--host', '127.0.0.1'] + args,
            env, self.log_path)
        deadline = t0 + max(min(ready_timeout_s, remaining()), 1.0)
        while time.monotonic() < deadline and self.proc.poll() is None:
            try:
                status, _ = http_json(f'{self.url}/health', timeout=5)
            except (OSError, ValueError):
                status = None
            if status == 200:
                self.ready_s = round(time.monotonic() - t0, 1)
                return
            time.sleep(0.5)

    def generate(self, tokens, max_new):
        return http_json(f'{self.url}/generate',
                         {'tokens': [tokens], 'max_new_tokens': max_new,
                          'temperature': 0.0}, timeout=300)

    def health(self):
        return http_json(f'{self.url}/health', timeout=30)[1]

    def why(self, tokens) -> str:
        """After a failed drive: what one streamed request answers (the
        engine reports its failure in-band), for the phase's line."""
        req = urllib.request.Request(
            f'{self.url}/generate', headers={
                'Content-Type': 'application/json'},
            data=json.dumps({'tokens': [tokens], 'max_new_tokens': 2,
                             'stream': True}).encode())
        try:
            with urllib.request.urlopen(req, timeout=120) as r:
                return r.read().decode('utf-8', 'replace')[-1500:]
        except (OSError, ValueError) as e:
            return f'{type(e).__name__}: {e}'[:1500]

    def stop(self):
        """SIGTERM -> drain -> the exit code (None: had to be killed)."""
        rc = None
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                rc = self.proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                pass
        else:
            rc = self.proc.returncode
        kill_group(self.proc)
        return rc

    def log(self) -> str:
        with open(self.log_path, encoding='utf-8', errors='replace') as f:
            return f.read()


# -- phases -----------------------------------------------------------------


class Smoke:

    def __init__(self, rehearse: bool):
        self.rehearse = rehearse
        self.cfg = REHEARSAL if rehearse else REAL
        self.want = 'cpu' if rehearse else 'tpu'
        self.device = None      # from the `devices` phase
        self.failed = []
        self.greedy = {}        # phase -> tokens of the greedy request
        self.cold_compile_s = None  # train-1chip's

    def env(self, **extra):
        return child_env(self.rehearse, **extra)

    def report(self, phase, t0, ok, device=None, log_text='', **checks):
        device = device or self.device or {}
        line = {'phase': phase, 'ok': bool(ok),
                'platform': device.get('platform'),
                'device_kind': device.get('device_kind'),
                'device_count': device.get('device_count'),
                'seconds': round(time.monotonic() - t0, 1), **checks}
        if self.rehearse:
            line['rehearsal'] = True
        print(json.dumps(line), flush=True)
        if not ok:
            self.failed.append(phase)
            if log_text:
                print(f'--- {phase}: end of the child\'s log ---\n'
                      f'{log_text[-3000:]}', file=sys.stderr, flush=True)
        return ok

    def on_wanted_device(self, text, checks) -> dict:
        """The child's own first device line; records why it is wrong."""
        lines = device_lines(text)
        device = lines[0] if lines else {}
        if device.get('platform') != self.want:
            checks['error'] = (f'child reported platform '
                               f'{device.get("platform")!r}, not '
                               f'{self.want!r}')
        if FALLBACK_TAG in text:
            checks['error'] = 'a Pallas kernel fell back to its reference'
        return device

    # devices ---------------------------------------------------------------

    def devices(self) -> bool:
        t0 = time.monotonic()
        rc, text = run_child(
            'devices', [sys.executable, '-m', 'skypilot_tpu.utils.jax_env'],
            self.env(), 180)
        checks = {'exit': rc}
        device = self.on_wanted_device(text, checks)
        ok = rc == 0 and 'error' not in checks
        if ok:
            self.device = device
        cache = prefixed_json(text, '[compile-cache] ')
        return self.report('devices', t0, ok, device, text,
                           compile_cache=cache[0] if cache else None,
                           **checks)

    # train -----------------------------------------------------------------

    def train_cmd(self, steps, extra=()):
        c = self.cfg
        return [sys.executable, '-m', 'skypilot_tpu.train.run',
                '--model', c['model'], '--seq-len', str(c['seq']),
                '--global-batch-size', str(c['batch']),
                '--steps', str(steps), '--log-every', '1', *extra]

    def check_train(self, text, steps, checks, want_balance=False):
        """Shared by train-*, and by launch-local on its run.log."""
        device = self.on_wanted_device(text, checks)
        got = train_steps(text)
        losses = [l for l, _ in got]
        secs = [s for _, s in got]
        ln_v = math.log(self.cfg['vocab'])
        checks.update(losses=losses, step_s=secs,
                      done='[train] done' in text)
        if secs[2:]:
            # Steps 1 and 2 both compile (the donated state comes back
            # with other shardings than init gave it); 3.. are steady.
            steady = statistics.median(secs[2:])
            checks['steady_step_s'] = round(steady, 3)
            checks['compile_s'] = round(sum(secs[:2]) - 2 * steady, 1)
        ok = (len(losses) == steps and checks['done']
              and all(math.isfinite(l) and abs(l - ln_v) <= 1.0
                      for l in losses)
              and 'error' not in checks)
        closing = device_lines(text)[-1:] or [{}]
        in_use = closing[0].get('bytes_in_use')
        if in_use:
            checks['bytes_in_use'] = in_use
        if want_balance and not self.rehearse:  # the CPU keeps no stats
            checks['balanced'] = balanced(in_use)
            ok = ok and checks['balanced']
        return ok, device

    def train(self, phase, extra=(), want_balance=False) -> bool:
        t0 = time.monotonic()
        rc, text = run_child(phase, self.train_cmd(6, extra), self.env(),
                             420)
        checks = {'exit': rc}
        ok, device = self.check_train(text, 6, checks, want_balance)
        if phase == 'train-1chip':
            self.cold_compile_s = checks.get('compile_s')
        return self.report(phase, t0, ok and rc == 0, device, text,
                           **checks)

    # serve -----------------------------------------------------------------

    def serve(self, phase, args=(), model=None, shared_prefix=False,
              want_balance=False) -> bool:
        """Start a replica, drive it, stop it."""
        t0 = time.monotonic()
        c = self.cfg
        model = model or c['model']
        checks = {}
        replica = Replica(phase, ['--model', model, *args], self.env(),
                          480)
        ok = replica.ready_s is not None
        checks['ready_s'] = replica.ready_s
        try:
            if ok:
                ok = self.drive_loadgen(phase, replica, checks)
            if ok:
                ok = self.drive_greedy(phase, replica, checks)
            if ok and shared_prefix:
                ok = self.drive_shared_prefix(replica, checks)
            if not ok and replica.ready_s is not None:
                checks['why'] = replica.why(
                    prompt_tokens(9, c['prompt'], c['vocab']))
            if ok:
                health = replica.health()
                device = health.get('device') or {}
                engine = health.get('engine') or {}
                checks.update(
                    health_platform=device.get('platform'),
                    tokens_emitted=engine.get('tokens_emitted'),
                    failures=engine.get('failures'),
                    compile_cache=(health.get('compile_cache')
                                   or {}).get('dir'))
                sent = checks.get('requests_sent', 0)
                # Every request sent ran to its last token in the
                # engine, and the engine never failed its waiters.
                ok = (device.get('platform') == self.want
                      and engine.get('failures') == 0
                      and engine.get('tokens_emitted', 0) >= sent * c['new']
                      and engine.get('active_slots') == 0)
                if shared_prefix:
                    checks['prefix_hits'] = engine['prefix_share']['hits']
                    ok = ok and checks['prefix_hits'] > 0
                # On the chip the bf16 pool is read by the kernel.
                path = checks['decode_attention'] = engine.get(
                    'decode_attention')
                ok = ok and (self.rehearse or path == 'paged_kernel')
                if device.get('bytes_in_use'):
                    checks['bytes_in_use'] = device['bytes_in_use']
                if want_balance and not self.rehearse:
                    checks['balanced'] = balanced(
                        device.get('bytes_in_use'))
                    ok = ok and checks['balanced']
        finally:
            checks['exit'] = replica.stop()
        text = replica.log()
        device = self.on_wanted_device(text, checks)
        ok = ok and checks['exit'] == 0 and 'error' not in checks
        return self.report(phase, t0, ok, device, text, **checks)

    def drive_loadgen(self, phase, replica, checks) -> bool:
        c = self.cfg
        n = 8
        rc, text = run_child(
            f'{phase}.loadgen',
            [sys.executable, '-m', 'skypilot_tpu.serve.loadgen',
             '--url', replica.url, '--vocab', str(c['vocab']),
             '--prompt-len', str(c['prompt']),
             '--max-new-tokens', str(c['new']), '--requests', str(n),
             '--concurrency', '4', '--stream'], self.env(), 300)
        try:
            out = json.loads(text.strip().splitlines()[-1])
        except (ValueError, IndexError):
            out = {}
        checks['loadgen'] = {k: out.get(k) for k in (
            'ok', 'new_tokens', 'wall_s', 'p50_latency_s', 'p50_ttft_s')}
        checks['requests_sent'] = n
        return (rc == 0 and out.get('ok') == n
                and out.get('new_tokens') == n * c['new'])

    def drive_greedy(self, phase, replica, checks) -> bool:
        """One greedy /generate, three times: exactly the number of
        tokens asked for, and the last two identical. The first
        prefills the whole prompt and the later ones only its last
        token over the prefix trie's shared blocks — two bf16 paths
        that agree to tolerance, not to the token (PR 26: with the decode kernel a 0.02 gap between the
        float32 reference's two best logits flipped at the 22nd token;
        the reference's best was the later requests')."""
        c = self.cfg
        prompt = prompt_tokens(1, c['prompt'], c['vocab'])
        n = 3
        t0 = time.monotonic()
        replies = [replica.generate(prompt, c['new']) for _ in range(n)]
        checks['greedy_s'] = round((time.monotonic() - t0) / n, 3)
        checks['requests_sent'] = checks.get('requests_sent', 0) + n
        rows = [body.get('tokens', [[]])[0] for _, body in replies]
        self.greedy[phase] = rows[0]
        checks['greedy_identical'] = rows[-2] == rows[-1]
        # for the record, not a check
        checks['greedy_miss_same_as_hit'] = rows[0] == rows[1]
        if phase != 'serve':  # for the record, not a check: a mesh
            # matches to tolerance, not to the token
            checks['greedy_same_as_serve'] = (
                rows[0] == self.greedy.get('serve'))
        return (all(status == 200 for status, _ in replies)
                and all(len(r) == c['new'] for r in rows)
                and rows[-2] == rows[-1]
                and all(0 <= t < c['vocab'] for r in rows for t in r))

    def drive_shared_prefix(self, replica, checks) -> bool:
        """Two requests sharing a head: the second must hit the trie."""
        c = self.cfg
        head = prompt_tokens(2, c['head'], c['vocab'])
        ok = True
        for seed in (3, 4):
            status, body = replica.generate(
                head + prompt_tokens(seed, c['prompt'], c['vocab']),
                c['new'])
            ok = (ok and status == 200
                  and len(body.get('tokens', [[]])[0]) == c['new'])
        checks['requests_sent'] = checks.get('requests_sent', 0) + 2
        return ok

    # kernels ---------------------------------------------------------------

    def kernels(self, meshes=()) -> bool:
        t0 = time.monotonic()
        argv = [sys.executable, os.path.abspath(__file__),
                '--child', 'kernels']
        if self.rehearse:
            argv.append('--rehearse-cpu')
        for m in meshes:
            argv += ['--mesh', m]
        rc, text = run_child('kernels', argv, self.env(), 480)
        checks = {'exit': rc}
        device = self.on_wanted_device(text, checks)
        results = prefixed_json(text, '[kernel] ')
        checks['cases'] = results
        ok = (rc == 0 and results and all(r['ok'] for r in results)
              and 'error' not in checks)
        return self.report('kernels', t0, ok, device, text, **checks)

    # launch-local ----------------------------------------------------------

    def launch_local(self) -> bool:
        t0 = time.monotonic()
        argv = [sys.executable, os.path.abspath(__file__),
                '--child', 'launch-local',
                '--run', ' '.join(self.train_cmd(3))]
        rc, text = run_child('launch-local', argv, self.env(), 420)
        checks = {'exit': rc}
        found = prefixed_json(text, '[launch] ')
        out = found[-1] if found else {}
        run_log = out.pop('run_log', '')
        checks.update(out)
        ok, device = self.check_train(run_log, 3, checks)
        ok = (ok and rc == 0 and out.get('status') == 'SUCCEEDED'
              and out.get('framework_processes_left') == [])
        # Same compile cache as train-1chip: the second run's compile
        # seconds against the first's.
        checks['compile_s_cold_train_1chip'] = self.cold_compile_s
        return self.report('launch-local', t0, ok, device,
                           text + run_log, **checks)

    # all of it -------------------------------------------------------------

    def skip(self, phase) -> None:
        n = (self.device or {}).get('device_count')
        line = {'phase': phase, 'skipped': f'{n} device(s)'}
        if self.rehearse:
            line['rehearsal'] = True
        print(json.dumps(line), flush=True)

    def run(self) -> int:
        if not self.devices():
            print(f'chip_smoke: JAX found no {self.want} here; nothing '
                  'was run', file=sys.stderr)
            return 1
        four = self.device['device_count'] >= 4
        self.train('train-1chip')
        self.serve('serve', shared_prefix=True)
        self.kernels(meshes=('fsdp=4', 'data=2,tensor=2') if four else ())
        self.launch_local()
        if four:
            batch8 = ['--global-batch-size', str(2 * self.cfg['batch'])]
            self.train('train-4chip-fsdp4', ['--mesh', 'fsdp=4', *batch8],
                       want_balance=True)
            self.train('train-4chip-data2-tensor2',
                       ['--mesh', 'data=2,tensor=2', *batch8],
                       want_balance=True)
            self.serve('serve-tp4', args=['--tp', '4'],
                       model=self.cfg['tp_model'], want_balance=True)
        else:
            self.skip('train-4chip')
            self.skip('serve-tp4')
        if self.failed:
            print(f'chip_smoke: failed phases: {self.failed}',
                  file=sys.stderr)
            return 1
        final = {'ok': True, 'device': {
            'platform': self.device['platform'],
            'kind': self.device['device_kind'],
            'count': self.device['device_count']}}
        if self.rehearse:
            final['rehearsal'] = True
        print(json.dumps(final), flush=True)
        return 0


# -- the two children that are code of this file ----------------------------


def child_kernels(rehearse: bool, meshes) -> int:
    """Compile and run each Pallas kernel against its jnp reference;
    lower the train step and look for the Mosaic call. One line per
    case: '[kernel] {"case": ..., "ok": ..., "err": ..., "tol": ...}'."""
    from skypilot_tpu.utils import jax_env
    jax_env.init_backend()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from skypilot_tpu.ops import attention, decode_attention
    from skypilot_tpu.train import run as train_run

    c = REHEARSAL if rehearse else REAL
    interpret = rehearse  # the CPU rehearsal asks for the interpreter
    all_ok = True

    def emit(case, ok, **kw):
        nonlocal all_ok
        all_ok = all_ok and ok
        print('[kernel] ' + json.dumps({'case': case, 'ok': bool(ok),
                                        **kw}), flush=True)

    def guarded(case, fn):
        try:
            fn()
        except Exception as e:  # noqa: BLE001 — a case Mosaic refuses is
            # a finding to print, and the other cases still run
            emit(case, False, error=f'{type(e).__name__}: {e}'[:1500])

    def rel_err(got, want):
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        return float(np.abs(got - want).max()
                     / (np.abs(want).max() + 1e-6))

    # bf16 inputs, fp32 accumulation on both sides: the kernel and the
    # reference differ by a few bf16 roundings of the probabilities.
    tol = 2e-2

    def flash_case(b, hq, hkv, s, d):
        ks = jax.random.split(jax.random.PRNGKey(s), 4)
        q, g = (jax.random.normal(k, (b, hq, s, d), jnp.bfloat16)
                for k in (ks[0], ks[3]))
        k, v = (jax.random.normal(kk, (b, hkv, s, d), jnp.bfloat16)
                for kk in (ks[1], ks[2]))

        def flash(q_, k_, v_):
            return attention.flash_attention(q_, k_, v_, causal=True,
                                             interpret=interpret)

        def reference(q_, k_, v_):
            return attention.attention_reference(q_, k_, v_, True)

        def fwd_and_grads(fn):
            def both(q_, k_, v_, g_):
                return fn(q_, k_, v_), jax.grad(
                    lambda *a: jnp.sum(fn(*a).astype(jnp.float32)
                                       * g_.astype(jnp.float32)),
                    argnums=(0, 1, 2))(q_, k_, v_)
            # skylint: allow-jit(one-shot numerics check, not a program)
            return jax.jit(both)

        out, grads = fwd_and_grads(flash)(q, k, v, g)
        # Reference one batch row at a time: its S x S logits are the
        # memory the kernel exists to avoid.
        ref = fwd_and_grads(reference)
        errs = {'fwd': 0.0, 'dq': 0.0, 'dk': 0.0, 'dv': 0.0}
        for i in range(b):
            sl = slice(i, i + 1)
            ref_o, ref_g = ref(q[sl], k[sl], v[sl], g[sl])
            errs['fwd'] = max(errs['fwd'], rel_err(out[sl], ref_o))
            for name, got, want in zip(('dq', 'dk', 'dv'), grads, ref_g):
                errs[name] = max(errs[name], rel_err(got[sl], want))
        emit(f'flash fwd+bwd B{b} Hq{hq} Hkv{hkv} S{s} D{d}',
             all(np.isfinite(e) and e <= tol for e in errs.values()),
             err={k_: round(e, 5) for k_, e in errs.items()}, tol=tol)

    def pool_layout(slots, blocks, block, max_blocks):
        """(valid [slots], tables [slots, max_blocks]) of a pool as the
        engine leaves it: live rows of every length class, every other
        slot empty, a full first block shared by two rows (both append
        elsewhere: the engine forks a shared tail), tables padded with
        the junk sink."""
        max_len = max_blocks * block
        valid = np.zeros((slots,), np.int32)
        lens = [1, block - 1, block, block + 1, max_len // 2 + 3, max_len]
        for i, slot in enumerate(range(0, slots, 2)):
            valid[slot] = lens[i % len(lens)]
        tables = np.zeros((slots, max_blocks), np.int32)
        free = iter(range(1, blocks))
        for slot in range(slots):
            n = -(-int(valid[slot]) // block)
            tables[slot, :n] = [next(free) for _ in range(n)]
        past_first = np.flatnonzero(valid > block)
        if len(past_first) > 1:
            tables[past_first[1], 0] = tables[past_first[0], 0]
        return valid, tables

    def paged_case(slots, blocks, block, max_blocks, hq, hkv, d, layers=2):
        """``paged_decode`` (the step's row written into layer 1 of the
        pools, then attended) over pools laid out as the engine leaves
        them (live rows of every length class, empty slots, a prefix
        shared by two rows, tables padded with the junk sink) against
        the row scatter + gather + einsum path on the same pools: the
        output within ``tol``, the pools to the bit."""
        from skypilot_tpu.models import paged as paged_lib
        key = jax.random.PRNGKey(slots)
        q = jax.random.normal(key, (slots, hq, d), jnp.bfloat16)
        kp, vp = (jax.random.normal(jax.random.fold_in(key, i),
                                    (layers, blocks, hkv, block, d),
                                    jnp.bfloat16) for i in (1, 2))
        kn, vn = (jax.random.normal(jax.random.fold_in(key, i),
                                    (slots, hkv, d), jnp.bfloat16)
                  for i in (3, 4))
        valid, tables = pool_layout(slots, blocks, block, max_blocks)
        args = (q, kn, vn, kp, vp, jnp.asarray(tables), jnp.asarray(valid))
        assert interpret or decode_attention.paged_fits(
            slots, max_blocks, block, d, kp.dtype)
        # skylint: allow-jit(one-shot numerics check, not a program)
        got, k_got, v_got = jax.jit(
            lambda q_, kn_, vn_, k_, v_, t_, n_:
            decode_attention.paged_decode(
                q_, kn_, vn_, k_, v_, jnp.int32(1), t_, n_,
                interpret=interpret))(*args)

        def reference(q_, kn_, vn_, k_, v_, t_, n_):
            # the scatter's junk aside: the kernel writes no inactive row
            k_, v_ = (paged_lib.pool_write(
                pool, 1, t_, n_ - 1, new[:, :, None], n_ > 0).at[0, 0].set(
                    pool[0, 0]) for pool, new in ((k_, kn_), (v_, vn_)))
            return paged_lib._gather_attention(
                q_[:, None], k_, v_, None, None, 1, t_, n_ - 1)[:, 0], k_, v_

        # skylint: allow-jit(one-shot numerics check, not a program)
        want, k_want, v_want = jax.jit(reference)(*args)
        live = valid > 0
        err = rel_err(np.asarray(got, np.float32)[live],
                      np.asarray(want, np.float32)[live])
        pools_equal = bool(jnp.array_equal(k_got, k_want)
                           and jnp.array_equal(v_got, v_want))
        emit(f'paged_decode B{slots} NB{blocks} P{block} MB{max_blocks} '
             f'Hq{hq} Hkv{hkv} D{d} bf16',
             np.isfinite(err) and err <= tol and pools_equal
             and not np.asarray(got, np.float32)[~live].any(),
             err=round(err, 5), tol=tol, pools_equal=pools_equal)

    def mla_case(slots, blocks, block, max_blocks, heads=32, rank=512,
                 rope=64, layers=2):
        """``mla_decode`` (the absorbed latent step: one shared 576-wide
        row a position, values its first 512 columns) over layer 1 of a
        latent pool laid out as ``paged_case`` lays its own, against the
        jnp step over the gathered view."""
        from skypilot_tpu.models import mla_moe
        cfg = mla_moe.MlaMoeConfig(n_heads=heads, kv_lora_rank=rank,
                                   qk_rope_dim=rope)
        key = jax.random.PRNGKey(slots + 1)
        q = jax.random.normal(key, (slots, heads, rank + rope), jnp.bfloat16)
        pool = jax.random.normal(
            jax.random.fold_in(key, 1),
            (layers, blocks, 1, block, cfg.latent_width), jnp.bfloat16)
        pool = pool.at[..., rank + rope:].set(0)
        valid, tables = pool_layout(slots, blocks, block, max_blocks)
        args = (q, pool, jnp.asarray(tables), jnp.asarray(valid))
        assert interpret or decode_attention.mla_fits(
            slots, max_blocks, block, pool.dtype)
        scale = mla_moe.softmax_scale(cfg)
        # skylint: allow-jit(one-shot numerics check, not a program)
        got = jax.jit(lambda q_, p_, t_, n_: decode_attention.mla_decode(
            q_, p_, jnp.int32(1), t_, n_, rank, scale,
            interpret=interpret))(*args)
        # skylint: allow-jit(one-shot numerics check, not a program)
        want = jax.jit(lambda q_, p_, t_, n_: mla_moe._absorbed_view(
            cfg, q_, mla_moe._pool_view(p_, 1, t_), n_))(*args)
        live = valid > 0
        err = rel_err(np.asarray(got, np.float32)[live],
                      np.asarray(want, np.float32)[live])
        emit(f'mla_decode B{slots} NB{blocks} P{block} MB{max_blocks} '
             f'H{heads} R{rank}+{rope} bf16',
             np.isfinite(err) and err <= tol
             and not np.asarray(got, np.float32)[~live].any(),
             err=round(err, 5), tol=tol)

    def kda_case(layers, slots, heads, d):
        """``kda_step`` on the last layer of a state, every other slot
        live, against the plain XLA recurrence (``kda.recur``): float32
        on both sides, so to rounding; slots not live and the other
        layers to the bit."""
        from skypilot_tpu.models import kda
        ks = jax.random.split(jax.random.PRNGKey(slots), 6)
        state = jax.random.normal(ks[0], (layers, slots, heads, d, d))
        q, k = (kda._l2(jax.random.normal(ks[i], (slots, heads, d)))
                for i in (1, 2))
        v = jax.random.normal(ks[3], (slots, heads, d))
        g = -jnp.exp(jax.random.normal(ks[4], (slots, heads, d)) - 1.0)
        beta = jax.nn.sigmoid(jax.random.normal(ks[5], (slots, heads)))
        live = np.arange(slots) % 2 == 0
        assert interpret or decode_attention.kda_fits(state.shape,
                                                      state.dtype)
        # skylint: allow-jit(one-shot numerics check, not a program)
        got_o, got_s = jax.jit(lambda *a: decode_attention.kda_step(
            a[0], jnp.int32(layers - 1), *a[1:], interpret=interpret))(
            state, q, k, v, g, beta, jnp.asarray(live))
        # skylint: allow-jit(one-shot numerics check, not a program)
        want_o, want_s = jax.jit(kda.recur)(state[-1], q, k, v, g, beta)
        err = max(rel_err(np.asarray(got_o)[live], np.asarray(want_o)[live]),
                  rel_err(np.asarray(got_s[-1])[live],
                          np.asarray(want_s)[live]))
        untouched = bool(
            jnp.array_equal(got_s[:-1], state[:-1])
            and jnp.array_equal(got_s[-1][~live], state[-1][~live]))
        emit(f'kda_step L{layers} B{slots} H{heads} D{d} f32',
             np.isfinite(err) and err <= 1e-5 and untouched
             and not np.asarray(got_o)[~live].any(),
             err=float(f'{err:.3g}'), tol=1e-5, untouched=untouched)

    hq, hkv, d = c['hq'], c['hkv'], c['d']
    guarded('paged_decode', lambda: paged_case(**c['paged']))
    guarded('mla_decode', lambda: mla_case(**c['mla']))
    guarded('kda_step', lambda: kda_case(**c['kda']))
    for s in c['flash_seqs']:
        guarded(f'flash S{s}', lambda s=s: flash_case(2, hq, hkv, s, d))
    # The VMEM cap itself, as the code has it: one group.
    flash_cap = c.get('flash_cap_seq') or attention._VMEM_CAP_ELEMS // d
    guarded('flash at the cap', lambda: flash_case(
        1, hq // hkv, 1, flash_cap, d))

    # The train step as train/run.py builds it: the Mosaic custom call
    # must be in its HLO (the reference did not stand in), and under a
    # mesh its operands must be shard-shaped (nothing gathered).
    def lower_case(mesh_spec):
        batch = c['batch'] * (2 if mesh_spec else 1)
        argv = ['--model', c['model'], '--seq-len', str(c['seq']),
                '--global-batch-size', str(batch)]
        if mesh_spec:
            argv += ['--mesh', mesh_spec]
        cfg, trainer = train_run.trainer_from_args(
            train_run.build_parser().parse_args(argv))
        state = jax.eval_shape(lambda: trainer.init_state(seed=0))
        tokens = jax.ShapeDtypeStruct((batch, c['seq']), jnp.int32)
        hlo = trainer.compiled_step().lower(state, tokens).as_text()
        calls = [l for l in hlo.splitlines() if 'tpu_custom_call' in l]
        axes = dict(zip(trainer.mesh.axis_names,
                        trainer.mesh.devices.shape))
        m = cfg.model
        want = (batch // (axes['data'] * axes['fsdp']),
                m.n_heads // axes['tensor'], c['seq'], m.head_dim)
        shape = 'x'.join(str(n) for n in want) + 'x'
        at_shard_shape = [l for l in calls if f'tensor<{shape}' in l]
        emit(f'train step HLO mesh={mesh_spec or "1 device"}',
             rehearse or (bool(calls) and bool(at_shard_shape)),
             tpu_custom_calls=len(calls), q_shard_shape=list(want),
             calls_at_shard_shape=len(at_shard_shape))

    for mesh_spec in (None, *meshes):
        guarded(f'train step HLO mesh={mesh_spec}',
                lambda mesh_spec=mesh_spec: lower_case(mesh_spec))
    return 0 if all_ok else 1


def child_launch_local(run_cmd: str) -> int:
    """The orchestrator's own path, as in bench.py's provision probe:
    launch on the local provider, wait, read run.log, down. Prints
    '[launch] {...}' with the job's status and log."""
    state_dir = tempfile.mkdtemp(prefix='skytpu-smoke-')
    os.environ['SKYTPU_STATE_DIR'] = state_dir
    from skypilot_tpu import core, execution
    from skypilot_tpu.agent import job_lib, native
    from skypilot_tpu.backends.tpu_gang_backend import runtime_dir
    from skypilot_tpu.resources import Resources
    from skypilot_tpu.task import Task
    from skypilot_tpu.utils import tpu_doctor

    cluster = 'chip-smoke'
    out = {'status': None}
    task = Task('chip-smoke-train', run=run_cmd)
    task.set_resources(Resources(cloud='local'))
    try:
        job_id, _ = execution.launch(task, cluster_name=cluster,
                                     detach_run=True)
        deadline = time.monotonic() + 360
        while time.monotonic() < deadline:
            status = core.job_status(cluster, job_id)
            if status and job_lib.JobStatus(status).is_terminal():
                out['status'] = status
                break
            time.sleep(0.5)
        log = os.path.join(runtime_dir(cluster), 'jobs', str(job_id),
                           'run.log')
        try:
            with open(log, encoding='utf-8', errors='replace') as f:
                out['run_log'] = f.read()
        except OSError:
            out['run_log'] = ''
        # Which gang runner ran the job: the native supervisor built
        # from gangd.cc, or the pure-Python one.
        out['gang_runner'] = ('native gangd' if native.gang_binary()
                              else 'python')
    finally:
        try:
            core.down(cluster)
        finally:
            # Ours only (the fingerprint the daemons inherited): another
            # session's processes on a shared host are not this job's.
            # The cluster daemon exits on its own at its next 20 s tick
            # after the down; wait that long, no longer.
            deadline = time.monotonic() + 30
            while True:
                left = [p['cmdline'][:120]
                        for p in tpu_doctor.framework_processes()
                        if p['fingerprint'] == os.environ.get(
                            tpu_doctor.SESSION_ENV)]
                if not left or time.monotonic() > deadline:
                    break
                time.sleep(1.0)
            out['framework_processes_left'] = left
            shutil.rmtree(state_dir, ignore_errors=True)
            print('[launch] ' + json.dumps(out), flush=True)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    ap.add_argument('--rehearse-cpu', action='store_true',
                    help='same phases with --model tiny on the CPU, '
                         'kernels in interpret mode; proves the script, '
                         'not the chip')
    ap.add_argument('--child', choices=('kernels', 'launch-local'),
                    help=argparse.SUPPRESS)
    ap.add_argument('--mesh', action='append', default=[],
                    help=argparse.SUPPRESS)
    ap.add_argument('--run', help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child == 'kernels':
        return child_kernels(args.rehearse_cpu, args.mesh)
    if args.child == 'launch-local':
        return child_launch_local(args.run)
    os.makedirs(LOG_DIR, exist_ok=True)
    for sig in (signal.SIGTERM, signal.SIGINT):
        signal.signal(sig, _on_signal)
    try:
        return Smoke(args.rehearse_cpu).run()
    finally:
        cleanup()


if __name__ == '__main__':
    sys.exit(main())
