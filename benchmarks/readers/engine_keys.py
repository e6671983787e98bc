"""Numbers from keys of ``ContinuousEngine.stats()`` that only some
programs have (the snapshots the run takes at the window's edges). A
program without the key gives nothing to read."""
from __future__ import annotations

from typing import Optional


def read(ctx, stat: str) -> Optional[float]:
    s0, s1 = ctx.stats0 or {}, ctx.stats1 or {}
    if stat == 'kv_bytes_per_token':
        # what one token costs the cache over all layers, by the
        # program's own count
        return s1.get('kv_bytes_per_token')
    if stat == 'moe_load_max_over_mean':
        # the busiest expert's tokens over the mean expert's, over the
        # decode chunks of the window: 1 = perfectly even routing
        a, b = s0.get('moe_expert_load'), s1.get('moe_expert_load')
        if not b:
            return None
        load = [y - x for x, y in zip(a or [0] * len(b), b)]
        total = sum(load)
        return max(load) * len(load) / total if total > 0 else None
    raise ValueError(f'engine_keys reader: unknown stat {stat!r}')
