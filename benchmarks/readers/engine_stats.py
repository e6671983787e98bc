"""Numbers from ``ContinuousEngine.stats()``: counters as deltas over
the window, gauges as samples taken through it (traced run only)."""
from __future__ import annotations

from typing import Optional


def read(ctx, stat: str) -> Optional[float]:
    if stat == 'occupancy':
        if not ctx.samples:
            return None
        slots = float(ctx.mix['engine']['slots'])
        return 100.0 * sum(s['active'] for s in ctx.samples) / (
            len(ctx.samples) * slots)
    if stat == 'pool_used_peak':
        if not ctx.samples:
            return None
        return 100.0 * max(s['live_blocks'] / max(s['usable'], 1)
                           for s in ctx.samples)
    if stat == 'prefill_saved_share':
        if not ctx.stats0 or not ctx.stats1:
            return None
        saved = (ctx.stats1['prefill_tokens_saved']
                 - ctx.stats0['prefill_tokens_saved'])
        done = ctx.stats1['prefill_tokens'] - ctx.stats0['prefill_tokens']
        if saved + done <= 0:
            return None
        return 100.0 * saved / (saved + done)
    raise ValueError(f'engine_stats reader: unknown stat {stat!r}')
