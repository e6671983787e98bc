"""Numbers taken from the driver's own records on the host's clock:
the end-to-end metrics and the client's medians beside them."""
from __future__ import annotations

from typing import Optional

from benchmarks import timeline as tl


def read(ctx, stat: str) -> Optional[float]:
    if stat == 'setup_s':
        return ctx.parts.get('setup_s')
    if stat == 'train_tok_s':
        if not ctx.step_ends:
            return None
        return tl.train_tok_s(ctx.step_ends, ctx.t0, ctx.t1,
                              ctx.tokens_per_step, ctx.chips)
    if not ctx.records:
        return None
    if stat == 'out_tok_s':
        return tl.out_tok_s(ctx.records, ctx.t0, ctx.t1)
    if stat in ('ttft_p90_ms', 'tpot_p90_ms', 'ttft_p50_ms', 'tpot_p50_ms'):
        if not tl.counted(ctx.records):
            return None
        return tl.latency_metrics(ctx.records).get(stat)
    if stat == 'late_p99_ms':
        late = tl.lateness_ms(ctx.records)
        return tl.percentile(late, 99) if late else None
    if stat == 'admit_wait_p50_ms':
        waits = tl.admit_wait_ms(ctx.records)
        return tl.percentile(waits, 50) if waits else None
    raise ValueError(f'timeline reader: unknown stat {stat!r}')
