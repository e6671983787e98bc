"""Numbers reduced from the profiler's device trace of the traced
sub-window (``benchmarks/trace.py`` does the arithmetic)."""
from __future__ import annotations

import statistics
from typing import Optional

from benchmarks import trace as tr


def read(ctx, stat: str, program: str = '', match: str = '',
         steps_key: str = '') -> Optional[float]:
    t = ctx.trace
    if t is None or not t.devices:
        return None
    progs = ctx.programs
    if stat == 'idle_share':
        _, fullest, window = tr.busy_and_window(t)
        return 100.0 * (1.0 - fullest / window) if window > 0 else None
    if stat == 'program_ms':
        # Device time of one execution of ``program``; ``steps_key``
        # names the engine setting that says how many steps one
        # execution holds (a decode chunk is a scan of several).
        runs = tr.module_runs(t, progs, program)
        if not runs:
            return None
        per = 1
        if steps_key:
            per = int(ctx.mix.get('engine', {}).get(steps_key, 8))
        # The median: one cut-short execution at the window's edge
        # must not move a step's time.
        return 1e3 * statistics.median(runs) / per
    if stat == 'program_share':
        secs = tr.program_seconds(t, progs)
        busy = sum(v for v, _ in secs.values())
        if busy <= 0:
            return None
        part = sum(v for k, (v, _) in secs.items() if k.startswith(program))
        return 100.0 * part / busy
    if stat == 'op_share':
        ops = tr.ops_matching(t, match)
        secs = tr.program_seconds(t, progs)
        total = secs.get(program, (0.0, 0))[0]
        if not ops or total <= 0:
            return None
        return 100.0 * sum(o.dur for o in ops) / 1e9 / total
    if stat == 'exposed_collective_share':
        if len(t.devices) < 2:
            return None
        each = [tr.exposed_collective_s(d, t.t0, t.t1) for d in t.devices]
        runs = tr.module_runs(t, progs, program)
        if not runs:
            return None
        return 100.0 * (sum(each) / len(each)) / sum(runs)
    raise ValueError(f'device_trace reader: unknown stat {stat!r}')
