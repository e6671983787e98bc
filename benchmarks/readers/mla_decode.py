"""``mla_decode_roofline``: the latent decode kernel's share of its
roofline. The kernel is found in the trace by the name the program
gives its Pallas call (``mla_decode``); its work is
``roofline/mla_decode.py``'s for the positions live in the traced
window (the driver's own records). A trace without the kernel, or a
configuration without a latent cache, gives nothing."""
from __future__ import annotations

from typing import Optional

from benchmarks import timeline as tl
from benchmarks import trace as tr
from benchmarks.roofline import mla_decode, roofline_share


def read(ctx, match: str = '^mla_decode') -> Optional[float]:
    t = ctx.trace
    if t is None or not t.devices or 'kv_lora_rank' not in ctx.cfg:
        return None
    ops = tr.ops_matching(t, match)
    if not ops or not ctx.records:
        return None
    live, active = tl.live_tokens_mean(ctx.records, ctx.trace_t0,
                                       ctx.trace_t1)
    if active <= 0:
        return None
    flops, nbytes = mla_decode.ops_and_bytes(
        ctx.cfg['num_attention_heads'], ctx.cfg['kv_lora_rank'],
        ctx.cfg['qk_rope_head_dim'], live)
    per_call = sum(o.dur for o in ops) / len(ops) / 1e9
    return roofline_share(flops, nbytes, per_call, ctx.peaks)
