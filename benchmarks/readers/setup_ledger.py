"""What the program counts of its own set-up (``profiler.setup_totals``):
the seconds it traced, lowered and built (compiled, or loaded from the
persistent cache) programs, eager operations included, the builds, and
the persistent cache's misses, from the process's start to the instant
the window opened. The program keeps each stage's intervals on the wall
clock, so the count is cut at ``ctx.t0``: what the reference builds
after the window is left out. A program without the ledger gives
nothing to read."""
from __future__ import annotations

import time
from typing import Optional

STATS = ('trace_s', 'lower_s', 'build_s', 'builds', 'cache_misses')


def read(ctx, stat: str) -> Optional[float]:
    if stat not in STATS:
        raise ValueError(f'setup_ledger reader: unknown stat {stat!r}')
    from skypilot_tpu.observability import profiler
    totals = getattr(profiler, 'setup_totals', None)
    if totals is None or not ctx.t0:
        return None
    opened = time.time() - (time.perf_counter() - ctx.t0)
    return float(totals(until=opened, top=0)[stat])
