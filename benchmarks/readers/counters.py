"""Counts the program or the runtime keeps: compilations inside the
window (the jit caches' growth; must be 0) and the fullest chip's peak
memory."""
from __future__ import annotations

from typing import Optional


def read(ctx, stat: str) -> Optional[float]:
    if stat == 'compile_in_window':
        c0, c1 = ctx.compiles0, ctx.compiles1
        if isinstance(c0, dict):
            return float(sum(c1.get(k, 0) - c0.get(k, 0) for k in c1))
        return float(c1 - c0)
    if stat == 'peak_hbm_gb':
        return ctx.memory_peak / 1e9 if ctx.memory_peak else None
    raise ValueError(f'counters reader: unknown stat {stat!r}')
