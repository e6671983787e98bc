"""Numbers from the spans the engine's loop opens on the profiler's
clock (``engine.*`` in the trace's host plane): how much of the traced
window the host worked, and how much of it it sat waiting for the
device. A program that opens no such spans gives nothing to read."""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

from benchmarks import trace as tr

# Spans in which the loop does nothing but wait: for a decode chunk's
# tokens, for first tokens, for a request.
WAITING = ('engine.wait_chunk', 'engine.wait_firsts', 'engine.idle')
BLOCKED = ('engine.wait_chunk', 'engine.wait_firsts')


def engine_spans(t) -> Dict[str, List[Tuple[float, float]]]:
    """``engine.*`` spans by name as (start, end), those that reach
    into the traced window. A span that holds another of its own name
    is dropped: the traced run still wraps six engine methods from
    outside under the names the engine now opens itself, and each such
    pair is one span, the inner."""
    by_name: Dict[str, List[Tuple[float, float]]] = {}
    for s in t.spans:
        if s.name.startswith('engine.') and s.end > t.t0 and s.start < t.t1:
            by_name.setdefault(s.name, []).append((s.start, s.end))
    for name, spans in by_name.items():
        spans.sort(key=lambda se: (se[0], -se[1]))
        kept = []
        for i, (a, b) in enumerate(spans):
            nxt = spans[i + 1] if i + 1 < len(spans) else None
            if nxt is not None and nxt[0] >= a and nxt[1] <= b:
                continue        # holds the next one: the outer of a pair
            kept.append((a, b))
        by_name[name] = kept
    return by_name


def seconds(spans: Sequence[Tuple[float, float]], t0: float,
            t1: float) -> float:
    return sum(b - a for a, b in tr.union(spans, t0, t1)) / 1e9


def read(ctx, stat: str) -> Optional[float]:
    t = ctx.trace
    if t is None or t.t1 <= t.t0:
        return None
    spans = engine_spans(t)
    if not any(name in spans for name in WAITING):
        return None     # the wrapper's six names alone: not the engine's
    waiting = seconds([se for n in WAITING for se in spans.get(n, [])],
                      t.t0, t.t1)
    if stat == 'host_work_ms':
        chunks = len(spans.get('engine.dispatch_chunk', []))
        if not chunks:
            return None
        under = seconds([se for ses in spans.values() for se in ses],
                        t.t0, t.t1)
        return 1e3 * (under - waiting) / chunks
    if stat == 'blocked_share':
        blocked = seconds([se for n in BLOCKED for se in spans.get(n, [])],
                          t.t0, t.t1)
        return 100.0 * blocked / ((t.t1 - t.t0) / 1e9)
    raise ValueError(f'host_spans reader: unknown stat {stat!r}')
