"""Trainer step telemetry: a bounded JSONL spool per training process.

The write side rides the trainer's existing ``--log-every`` metrics fetch
(``train/run.py``): one record per log window — step time, tokens/s,
achieved MFU, loss — appended to a spool file under the job's runtime
dir. Pure file append, no device sync of its own; when the spool dir env
var is unset the writer is ``None`` and the trainer's behavior (including
stdout) is byte-identical to a telemetry-less build.

The read side is consumed by the per-cluster heartbeat daemon
(``agent/daemon.py``), which folds the newest window into its heartbeat
so the controller sees training *progress*, not just liveness.

Dependency-free by the observability-package charter: this module rides
inside the trainer, the gang driver, and the cluster daemon, and must
never import jax (a chip belongs to one process; a daemon touching jax
would take it from the trainer) or anything heavier than the stdlib.
"""
from __future__ import annotations

import json
import os
from typing import Any, Dict, List, Optional

# Spool location contract: the gang driver exports this per worker
# (pointing under the job's log dir); recipes may override it. Unset =>
# telemetry fully disabled.
ENV_DIR = 'SKYTPU_TRAIN_TELEMETRY_DIR'
SPOOL_FILE = 'train_telemetry.jsonl'
# Spool bound: one rotation generation is kept (``.1``), so disk usage is
# capped at ~2x this size per training process.
ENV_MAX_KB = 'SKYTPU_TRAIN_TELEMETRY_MAX_KB'
DEFAULT_MAX_KB = 512


def _max_bytes() -> int:
    try:
        return int(float(os.environ.get(ENV_MAX_KB,
                                        str(DEFAULT_MAX_KB))) * 1024)
    except ValueError:
        return DEFAULT_MAX_KB * 1024


def peak_flops_per_s() -> float:
    """Accelerator peak (FLOP/s) for MFU accounting. There is no portable
    in-band way to ask a device for its peak, so it travels as an env var
    (recipes/launch templates set it per accelerator type); 0 = unknown,
    MFU omitted."""
    try:
        return float(os.environ.get('SKYTPU_PEAK_FLOPS', '0'))
    except ValueError:
        return 0.0


def window_record(*, step: int, steps: int, window_s: float,
                  tokens_per_step: float, model_flops_per_step: float,
                  loss: Optional[float] = None,
                  ts: Optional[float] = None) -> Dict[str, Any]:
    """One log-window record from plain numbers (the trainer computes
    tokens/flops per step via its own helpers so this module never
    imports the model stack)."""
    import time
    window_s = max(window_s, 1e-9)
    rec: Dict[str, Any] = {
        'ts': round(ts if ts is not None else time.time(), 3),
        'step': int(step),
        'steps_in_window': int(steps),
        'window_s': round(window_s, 6),
        'step_time_s': round(window_s / max(steps, 1), 6),
        'tokens_per_s': round(tokens_per_step * steps / window_s, 3),
        'model_flops_per_s': round(
            model_flops_per_step * steps / window_s, 3),
    }
    if loss is not None:
        rec['loss'] = round(float(loss), 6)
    peak = peak_flops_per_s()
    if peak > 0:
        rec['mfu'] = round(rec['model_flops_per_s'] / peak, 6)
    return rec


def ckpt_record(*, op: str, step: int, seconds: float,
                stall_s: Optional[float] = None,
                nbytes: Optional[int] = None,
                source: Optional[str] = None,
                async_save: Optional[bool] = None,
                emergency: bool = False,
                ts: Optional[float] = None) -> Dict[str, Any]:
    """One checkpoint event (``op`` = 'save' | 'restore') from the ckpt
    manager. Rides the same spool as the window records; the ``kind``
    field keeps the two record families separable (window records have
    none — the PR-4 on-disk format predates it)."""
    import time
    rec: Dict[str, Any] = {
        'kind': 'ckpt',
        'op': op,
        'ts': round(ts if ts is not None else time.time(), 3),
        'step': int(step),
        'seconds': round(float(seconds), 6),
    }
    if stall_s is not None:
        rec['stall_s'] = round(float(stall_s), 6)
    if nbytes is not None:
        rec['nbytes'] = int(nbytes)
    if source is not None:
        rec['source'] = source
    if async_save is not None:
        rec['async'] = bool(async_save)
    if emergency:
        rec['emergency'] = True
    return rec


def ckpt_totals(records: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Fold a spool's ckpt records into the cumulative accounting the
    heartbeat ships and the goodput ledger attributes: seconds spent
    persisting (save_s), seconds the step loop actually stalled
    (stall_s — the async win is save_s >> stall_s), restore cost, and
    checkpoint freshness (last_step / last_save_ts)."""
    out: Dict[str, Any] = {'saves': 0, 'save_s': 0.0, 'stall_s': 0.0,
                           'restores': 0, 'restore_s': 0.0,
                           'last_step': 0, 'last_save_ts': 0.0}
    for rec in records:
        if rec.get('kind') != 'ckpt':
            continue
        if rec.get('op') == 'save':
            out['saves'] += 1
            out['save_s'] += float(rec.get('seconds') or 0.0)
            out['stall_s'] += float(rec.get('stall_s') or 0.0)
            out['last_step'] = max(out['last_step'],
                                   int(rec.get('step') or 0))
            out['last_save_ts'] = max(out['last_save_ts'],
                                      float(rec.get('ts') or 0.0))
        elif rec.get('op') == 'restore':
            out['restores'] += 1
            out['restore_s'] += float(rec.get('seconds') or 0.0)
    for k in ('save_s', 'stall_s', 'restore_s'):
        out[k] = round(out[k], 6)
    return out


def cluster_telemetry_summary(
        cluster_runtime_dir: str) -> Dict[str, Optional[Dict[str, Any]]]:
    """ONE pass over every job/rank spool under a cluster runtime dir:
    ``train`` = the newest training window (tagged with the job id and
    rank it came from; None without telemetry) and ``ckpt`` = the
    cumulative checkpoint accounting (None without ckpt records). The
    heartbeat needs both every tick and must not glob + re-parse the
    spools once per consumer."""
    import glob
    root = os.path.expanduser(cluster_runtime_dir)
    pattern = os.path.join(root, 'jobs', '*', 'telemetry', '*', SPOOL_FILE)
    newest_path, newest_mtime = None, -1.0
    newest_records: List[Dict[str, Any]] = []
    all_records: List[Dict[str, Any]] = []
    for path in glob.glob(pattern):
        try:
            mtime = os.stat(path).st_mtime
        except OSError:
            continue
        records = read_records(os.path.dirname(path))
        all_records.extend(records)
        if mtime > newest_mtime:
            newest_path, newest_mtime, newest_records = \
                path, mtime, records
    window = None
    if newest_path is not None:
        windows = [r for r in newest_records if 'kind' not in r]
        if windows:
            # .../jobs/<job_id>/telemetry/<rank>/train_telemetry.jsonl
            parts = newest_path.split(os.sep)
            try:
                window = dict(windows[-1], job_id=int(parts[-4]),
                              rank=parts[-2])
            except (ValueError, IndexError):
                window = dict(windows[-1])
    totals: Optional[Dict[str, Any]] = ckpt_totals(all_records)
    if not totals['saves'] and not totals['restores']:
        totals = None
    return {'train': window, 'ckpt': totals}


def ckpt_totals_for_cluster(
        cluster_runtime_dir: str) -> Optional[Dict[str, Any]]:
    """Cumulative ckpt accounting across every job/rank spool under a
    cluster runtime dir (goodput-ledger consumer). None when no spool
    holds a checkpoint record."""
    return cluster_telemetry_summary(cluster_runtime_dir)['ckpt']


class TelemetryWriter:
    """Append-only JSONL spool, bounded by one-generation rotation.

    Every failure path disables the writer instead of raising: telemetry
    must never take a training step down with it."""

    def __init__(self, spool_dir: str,
                 max_bytes: Optional[int] = None):
        import threading
        self._path = os.path.join(os.path.expanduser(spool_dir), SPOOL_FILE)
        self._max_bytes = max_bytes if max_bytes is not None else _max_bytes()
        self._broken = False
        # One writer instance is shared across threads (train loop,
        # ckpt commit worker, SIGTERM handler): the check-then-rotate
        # in emit() must not race itself, or a stale size check can
        # os.replace a fresh spool over the rotated generation.
        self._emit_lock = threading.Lock()
        try:
            os.makedirs(os.path.dirname(self._path), exist_ok=True)
            self._heal_torn_tail()
        except OSError:
            self._broken = True

    def _heal_torn_tail(self) -> None:
        """A process that crashed mid-append leaves an unterminated line;
        terminate it so this writer's first record does not fuse onto the
        torn one (the reader drops the torn line either way)."""
        try:
            with open(self._path, 'rb+') as f:
                f.seek(0, os.SEEK_END)
                if f.tell() == 0:
                    return
                f.seek(-1, os.SEEK_END)
                if f.read(1) != b'\n':
                    f.write(b'\n')
        except OSError:
            pass  # no spool yet

    @classmethod
    def from_env(cls) -> Optional['TelemetryWriter']:
        spool_dir = os.environ.get(ENV_DIR)
        if not spool_dir:
            return None
        return cls(spool_dir)

    def emit(self, record: Dict[str, Any]) -> None:
        if self._broken:
            return
        try:
            line = json.dumps(record, sort_keys=True)
            with self._emit_lock:
                try:
                    if os.path.getsize(self._path) + len(line) > \
                            self._max_bytes:
                        os.replace(self._path, self._path + '.1')
                except OSError:
                    pass  # no spool yet: nothing to rotate
                with open(self._path, 'a', encoding='utf-8') as f:
                    f.write(line + '\n')
        except (OSError, TypeError, ValueError):
            self._broken = True


def read_records(spool_dir: str) -> List[Dict[str, Any]]:
    """All records in a spool, oldest first (rotated generation included);
    malformed lines (torn writes) are skipped."""
    out: List[Dict[str, Any]] = []
    base = os.path.join(os.path.expanduser(spool_dir), SPOOL_FILE)
    for path in (base + '.1', base):
        try:
            with open(path, encoding='utf-8') as f:
                lines = f.readlines()
        except OSError:
            continue
        for line in lines:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue
            if isinstance(rec, dict):
                out.append(rec)
    return out


def latest_record(spool_dir: str) -> Optional[Dict[str, Any]]:
    """Newest WINDOW record — records carrying a ``kind`` (checkpoint
    events share the spool) must not masquerade as a training-progress
    window in heartbeats."""
    records = [r for r in read_records(spool_dir) if 'kind' not in r]
    return records[-1] if records else None


def latest_window_for_cluster(
        cluster_runtime_dir: str) -> Optional[Dict[str, Any]]:
    """Newest telemetry window across every job/rank spool under a cluster
    runtime dir (``jobs/<id>/telemetry/<rank>/``), tagged with the job id
    it came from. A cluster with no training telemetry returns None."""
    return cluster_telemetry_summary(cluster_runtime_dir)['train']
