"""From request records to the end-to-end numbers: pure arithmetic.

A record is what the driver saw of one request on the host's clock
(seconds, ``time.perf_counter``): when it was due or sent, when each
batch of tokens arrived and how many tokens it held, whether it
failed. Every end-to-end metric is taken over ALL counted requests and
over the WHOLE window; a request that failed or never finished ranks
above every finished one in a percentile.
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Dict, List, Optional, Sequence, Tuple

INF = float('inf')


@dataclasses.dataclass
class Record:
    rid: int
    prompt_len: int
    max_new: int
    counted: bool = True
    due: Optional[float] = None      # open loop: scheduled send
    sent: Optional[float] = None     # when submit() was called
    admitted: Optional[float] = None  # traced runs: prefill dispatched
    arrivals: List[Tuple[float, int]] = dataclasses.field(
        default_factory=list)        # (time, tokens in the batch)
    tokens: List[int] = dataclasses.field(default_factory=list)
    failed: bool = False
    prompt: Optional[List[int]] = None

    # The engine calls this from its own thread with each new batch.
    def on_tokens(self, new) -> None:
        self.arrivals.append((time.perf_counter(), len(new)))
        self.tokens.extend(int(t) for t in new)

    @property
    def origin(self) -> Optional[float]:
        """Open loop: the time the request was DUE (a stalled generator
        must not flatter the server). Closed loop: when it was sent."""
        return self.due if self.due is not None else self.sent

    @property
    def n_tokens(self) -> int:
        return sum(n for _, n in self.arrivals)

    @property
    def finished(self) -> bool:
        return (not self.failed) and self.n_tokens >= self.max_new

    @property
    def first(self) -> Optional[float]:
        return self.arrivals[0][0] if self.arrivals else None

    @property
    def last(self) -> Optional[float]:
        return self.arrivals[-1][0] if self.arrivals else None


def percentile(values: Sequence[float], q: float) -> float:
    """The smallest value with at least ``q`` percent of the sample at
    or below it (nearest rank): no interpolation, so a failure ranked
    ``inf`` is reported as ``inf`` and never averaged away."""
    if not values:
        raise ValueError('percentile of an empty sample')
    s = sorted(values)
    k = max(int(math.ceil(q / 100.0 * len(s))) - 1, 0)
    return s[min(k, len(s) - 1)]


def ttft_s(rec: Record) -> float:
    if not rec.finished or rec.first is None or rec.origin is None:
        return INF
    return rec.first - rec.origin


def tpot_s(rec: Record) -> Optional[float]:
    """(last token − first token) / (tokens − 1): the gap a reader of
    the stream feels, stalls included. ``inf`` for a failure; None for
    a one-token answer (no gap to speak of)."""
    if not rec.finished:
        return INF
    n = rec.n_tokens
    if n < 2:
        return None
    return (rec.last - rec.first) / (n - 1)


def counted(records: Sequence[Record]) -> List[Record]:
    return [r for r in records if r.counted]


def latency_metrics(records: Sequence[Record]) -> Dict[str, float]:
    """``ttft_p90_ms`` / ``tpot_p90_ms`` and the medians beside them,
    over all counted requests."""
    recs = counted(records)
    ttft = [ttft_s(r) for r in recs]
    tpot = [t for t in (tpot_s(r) for r in recs) if t is not None]
    out = {'ttft_p90_ms': percentile(ttft, 90) * 1e3,
           'ttft_p50_ms': percentile(ttft, 50) * 1e3}
    if tpot:
        out['tpot_p90_ms'] = percentile(tpot, 90) * 1e3
        out['tpot_p50_ms'] = percentile(tpot, 50) * 1e3
    return out


def tokens_in(records: Sequence[Record], t0: float, t1: float) -> int:
    """Output tokens that ARRIVED in [t0, t1), from every request,
    counted or not: what the system completed in the window."""
    return sum(n for r in records for t, n in r.arrivals if t0 <= t < t1)


def out_tok_s(records: Sequence[Record], t0: float, t1: float) -> float:
    return tokens_in(records, t0, t1) / (t1 - t0)


def lateness_ms(records: Sequence[Record]) -> List[float]:
    return [(r.sent - r.due) * 1e3 for r in counted(records)
            if r.due is not None and r.sent is not None]


def admit_wait_ms(records: Sequence[Record]) -> List[float]:
    return [(r.admitted - r.origin) * 1e3 for r in counted(records)
            if r.admitted is not None and r.origin is not None]


def attempted_failed(records: Sequence[Record]) -> Tuple[int, int]:
    recs = counted(records)
    return len(recs), sum(1 for r in recs if not r.finished)


def live_tokens_mean(records: Sequence[Record], t0: float, t1: float,
                     samples: int = 64) -> Tuple[float, float]:
    """(mean live K/V tokens, mean active requests) over [t0, t1),
    sampled: a request is live from its first token to its last and
    holds its prompt plus what it has emitted so far. Feeds the decode
    step's byte count, which is a function of the traffic."""
    if t1 <= t0:
        return 0.0, 0.0
    tot_tok = tot_act = 0.0
    for i in range(samples):
        t = t0 + (i + 0.5) * (t1 - t0) / samples
        for r in records:
            if r.first is None or r.first > t:
                continue
            if r.finished and r.last is not None and r.last < t:
                continue
            if r.failed:
                continue
            emitted = sum(n for at, n in r.arrivals if at <= t)
            tot_tok += r.prompt_len + emitted
            tot_act += 1
    return tot_tok / samples, tot_act / samples


def train_tok_s(step_ends: Sequence[float], t0: float, t_end: float,
                tokens_per_step: int, chips: int) -> float:
    """All tokens of all steps that ENDED in the window (the window
    closes on a device_get of the last step's loss) over the window's
    whole length, per chip."""
    steps = sum(1 for t in step_ends if t0 < t <= t_end)
    return steps * tokens_per_step / (t_end - t0) / chips
