"""The whole step's share of the chip's peak: model FLOPs of the work
done in the traced window over peak x chips x window. Serving counts
every prompt token the engine COMPUTED (not those a shared prefix
saved) and every output token it emitted, from ``engine.stats()``
deltas, at 2*N a token plus attention's pairs at the mean context;
training counts 6*N*tokens plus attention's, recomputation not
counted."""
from __future__ import annotations

from typing import Optional

from benchmarks import timeline as tl
from benchmarks.roofline import model


def read(ctx, kind: str) -> Optional[float]:
    window = ctx.trace_t1 - ctx.trace_t0
    if window <= 0 or not ctx.peaks:   # no chip: no share of a peak
        return None
    peak = ctx.peaks['bf16_flops'] * ctx.chips
    if kind == 'train':
        steps = sum(1 for e in ctx.step_ends
                    if ctx.trace_t0 < e <= ctx.trace_t1)
        if steps < 1 or ctx.trace is None:
            return None
        # Whole steps only: from the first to the last step end inside
        # the traced window.
        ends = [e for e in ctx.step_ends if ctx.trace_t0 < e <= ctx.trace_t1]
        if len(ends) < 2:
            return None
        flops = (len(ends) - 1) * model.train_flops_per_step(
            ctx.cfg, int(ctx.mix['batch']), int(ctx.mix['seq_len']))
        return 100.0 * flops / (peak * (ends[-1] - ends[0]))
    if kind == 'serve':
        s0, s1 = ctx.trace_stats0, ctx.trace_stats1
        if not s0 or not s1:
            return None
        prompt = s1['prefill_tokens'] - s0['prefill_tokens']
        out = s1['tokens_emitted'] - s0['tokens_emitted']
        if prompt + out <= 0:
            return None
        live, active = tl.live_tokens_mean(ctx.records, ctx.trace_t0,
                                           ctx.trace_t1)
        mean_ctx = live / active if active > 0 else 0.0
        # Output tokens attend the mean live context; computed prompt
        # tokens attend, on average, half of it.
        pairs = out * mean_ctx + prompt * mean_ctx / 2.0
        flops = model.forward_flops(ctx.cfg, prompt + out, pairs)
        return 100.0 * flops / (peak * window)
    raise ValueError(f'mfu reader: unknown kind {kind!r}')
