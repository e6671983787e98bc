"""Numbers from the timelines the engine stamps itself: every future
``ContinuousEngine.submit`` returns carries ``timeline``, the request's
phases on the clock the driver's own records use (``perf_counter``).
A program that stamps none gives nothing to read."""
from __future__ import annotations

from typing import List, Optional

from benchmarks import timeline as tl

# phase -> (stamp that opens it, stamp that closes it); the queue wait
# opens at the record's origin (open loop: when the request was DUE).
PHASES = {'queue_wait': (None, 'admit'),
          'prep': ('admit', 'prefill'),
          'first_wait': ('prefill', 'first')}


def phase_ms(records, phase: str) -> List[float]:
    """The phase's length for every counted finished request whose
    future carries a timeline that reached the phase's end."""
    opens, closes = PHASES[phase]
    out = []
    for r in tl.counted(records):
        line = getattr(getattr(r, 'future', None), 'timeline', None)
        if line is None or not r.finished:
            continue
        t0 = r.origin if opens is None else getattr(line, opens)
        t1 = getattr(line, closes)
        if t0 is not None and t1 is not None:
            out.append((t1 - t0) * 1e3)
    return out


def read(ctx, phase: str, q: float = 90) -> Optional[float]:
    waits = phase_ms(ctx.records, phase)
    return tl.percentile(waits, q) if waits else None
