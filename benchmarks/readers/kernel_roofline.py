"""A kernel's share of its roofline: the least time the chip could
take for the operations and bytes the ALGORITHM needs
(``benchmarks/roofline/``) over the device time the trace shows.
Returns nothing when the kernel is not in the trace — never 0."""
from __future__ import annotations

import statistics
from typing import Optional

from benchmarks import timeline as tl
from benchmarks import trace as tr
from benchmarks.roofline import decode_step, flash, model, roofline_share


def read(ctx, kernel: str, match: str = '') -> Optional[float]:
    """``match`` is the pattern that finds the kernel's events in the
    trace (on the instruction's name or its HLO text): the program
    gives its Pallas calls no name yet, so the metric's file tells them
    apart by their result signature."""
    t = ctx.trace
    if t is None or not t.devices:
        return None
    if kernel == 'decode_step':
        runs = tr.module_runs(t, ctx.programs, 'decode')
        if not runs or not ctx.records:
            return None
        steps = int(ctx.mix['engine'].get('chunk_steps', 8))
        step_s = statistics.median(runs) / steps
        live, active = tl.live_tokens_mean(ctx.records, ctx.trace_t0,
                                           ctx.trace_t1)
        if active <= 0:
            return None
        flops, nbytes = decode_step.ops_and_bytes(ctx.cfg, active, live,
                                                  ctx.chips)
        return roofline_share(flops, nbytes, step_s, ctx.peaks)
    if kernel in flash.KERNELS:
        if 'batch' not in ctx.mix:      # not a training cell
            return None
        _, _, hq, hkv, hd, _, _ = model.dims(ctx.cfg)
        b = int(ctx.mix['batch']) // ctx.chips
        for token, value in (('<B>', b), ('<HQ>', hq), ('<HKV>', hkv),
                             ('<S>', int(ctx.mix['seq_len'])), ('<D>', hd)):
            match = match.replace(token, str(value))
        ops = tr.ops_matching(t, match or kernel)
        if not ops:
            return None
        # Bookkeeping calls of the same signature that last microseconds
        # are not the kernel: keep events of at least a tenth of the
        # longest.
        floor = max(o.dur for o in ops) / 10.0
        ops = [o for o in ops if o.dur >= floor]
        flops, nbytes = flash.KERNELS[kernel](b, hq, hkv,
                                              int(ctx.mix['seq_len']), hd)
        per_call = sum(o.dur for o in ops) / len(ops) / 1e9
        return roofline_share(flops, nbytes, per_call, ctx.peaks)
    raise ValueError(f'kernel_roofline reader: unknown kernel {kernel!r}')
