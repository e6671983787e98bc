"""The one traffic generator: a mix is a data file, this reads it.

A mix (``benchmarks/traffic/<mix>.json``) states a ``loop`` and the
distributions of its lengths. Everything here is a pure function of
(mix, window seconds, vocabulary, seed) and touches neither JAX nor the
program, so the CPU tests check it whole.

Stratified by construction: for a mix and a window length the NUMBER of
requests and the MULTISETS of prompt lengths, answer lengths and gaps
between arrivals are fixed — they are the quantiles of the stated
distributions at the midpoints ``(i + 0.5) / n``. ``seed`` permutes
them and draws the token ids. Two seeds therefore offer the same work
in the same time and differ in order and content.

Loops:

* ``open``     — arrivals on a schedule (independent users): a ramp
  before the window, the window, a cool-down after it; only requests
  due inside the window are counted.
* ``sessions`` — closed loop of multi-turn sessions over tenants that
  share a system prompt; a client sends its next turn a think time
  after the reply. History is scripted (the "assistant" turns in a
  later prompt are seeded tokens of the scripted answer length, not the
  served ones), so every prompt is a function of the seed alone.
* ``backlog``  — closed loop that keeps ``depth`` requests outstanding
  (an offline batch): one is submitted for each that finishes.
* ``train``    — packed documents for the trainer: see ``train_batch``.
"""
from __future__ import annotations

import dataclasses
import statistics
from typing import Dict, Iterator, List, Optional

import numpy as np

_NORMAL = statistics.NormalDist()


def _midpoints(n: int) -> np.ndarray:
    return (np.arange(n, dtype=np.float64) + 0.5) / n


def quantile_lengths(spec: Dict, n: int) -> np.ndarray:
    """``n`` integer lengths: the quantiles of ``spec`` at the
    midpoints, clipped to [min, max]. ``dist``: ``lognormal`` (median,
    sigma), ``uniform`` or ``fixed`` (value)."""
    if n <= 0:
        return np.zeros((0,), np.int64)
    lo, hi = int(spec['min']), int(spec['max'])
    u = _midpoints(n)
    dist = spec['dist']
    if dist == 'lognormal':
        z = np.array([_NORMAL.inv_cdf(float(x)) for x in u])
        vals = float(spec['median']) * np.exp(float(spec['sigma']) * z)
    elif dist == 'uniform':
        vals = lo + (hi - lo) * u
    elif dist == 'fixed':
        vals = np.full((n,), float(spec['value']))
    else:
        raise ValueError(f'unknown length distribution {dist!r}')
    return np.clip(np.rint(vals), lo, hi).astype(np.int64)


def quantile_gaps(rate_rps: float, n: int, span_s: float) -> np.ndarray:
    """``n`` gaps between arrivals: the exponential's quantiles at the
    midpoints, scaled so that they sum to ``span_s`` exactly (the
    midpoint rule loses a little of the tail's mass)."""
    if n <= 0:
        return np.zeros((0,), np.float64)
    gaps = -np.log1p(-_midpoints(n)) / float(rate_rps)
    return gaps * (float(span_s) / gaps.sum())


def quantile_uniform(lo: float, hi: float, n: int) -> np.ndarray:
    return lo + (hi - lo) * _midpoints(n)


@dataclasses.dataclass
class Request:
    """One request as offered. ``due_s`` is relative to the window's
    opening (negative in the ramp); ``counted`` says whether the
    request is due inside the window. Closed loops leave ``due_s`` None:
    the driver stamps the send time."""
    rid: int
    prompt: List[int]
    max_new: int
    due_s: Optional[float] = None
    counted: bool = True
    session: Optional[int] = None
    turn: Optional[int] = None
    think_s: float = 0.0


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), int(stream)])


def _ids(rng: np.random.Generator, n: int, vocab: int) -> List[int]:
    return rng.integers(0, vocab, size=int(n), dtype=np.int64).tolist()


# -- open loop ---------------------------------------------------------------


def _open_phase(mix: Dict, n: int, span_s: float, start_s: float,
                rng: np.random.Generator, vocab: int, counted: bool,
                rid0: int) -> List[Request]:
    prompts = rng.permutation(quantile_lengths(mix['prompt'], n))
    answers = rng.permutation(quantile_lengths(mix['answer'], n))
    gaps = rng.permutation(quantile_gaps(mix['rate_rps'], n, span_s))
    # A request arrives at the END of its gap less half of it on
    # average; centring keeps the first and last arrivals inside.
    due = start_s + np.cumsum(gaps) - gaps / 2.0
    return [Request(rid=rid0 + i, prompt=_ids(rng, prompts[i], vocab),
                    max_new=int(answers[i]), due_s=float(due[i]),
                    counted=counted) for i in range(n)]


def open_schedule(mix: Dict, seconds: float, vocab: int,
                  seed: int) -> List[Request]:
    """Ramp, window and cool-down of an open-loop mix, in due order."""
    rate = float(mix['rate_rps'])
    ramp_s = float(mix.get('ramp_s', 0.0))
    cool_s = float(mix.get('cooldown_s', 0.0))
    n_ramp = int(round(rate * ramp_s))
    n_win = int(round(rate * seconds))
    n_cool = int(round(rate * cool_s))
    out = _open_phase(mix, n_ramp, ramp_s, -ramp_s, _rng(seed, 1), vocab,
                      False, 0)
    out += _open_phase(mix, n_win, seconds, 0.0, _rng(seed, 2), vocab,
                       True, n_ramp)
    out += _open_phase(mix, n_cool, cool_s, seconds, _rng(seed, 3), vocab,
                       False, n_ramp + n_win)
    return out


# -- sessions (closed loop) --------------------------------------------------


def pad_width(n: int, lo: int = 16) -> int:
    """Power-of-two padded width of an ``n``-token prefill: how a
    bucketing server pads (stated in the mix as ``pad_lo``)."""
    b = lo
    while b < n:
        b *= 2
    return b


class SessionScript:
    """Sessions as a function of (mix, seed): ``turns(k)`` gives
    session ``k``'s requests. The pool of (message, answer, think)
    triplets is a stratified grid permuted by the seed; session ``k``
    takes ``turns`` consecutive triplets, cyclically."""

    def __init__(self, mix: Dict, vocab: int, seed: int):
        self.mix = mix
        self.vocab = vocab
        self.seed = seed
        self.n_turns = int(mix['turns'])
        self.tenants = int(mix['tenants'])
        self.max_len = int(mix['engine']['max_len'])
        self.block = int(mix['engine'].get('kv_block', 16))
        self.pad_lo = int(mix.get('pad_lo', 16))
        pool = int(mix['clients']) * self.n_turns
        rng = _rng(seed, 11)
        self._msg = rng.permutation(quantile_lengths(mix['message'], pool))
        self._ans = rng.permutation(quantile_lengths(mix['answer'], pool))
        think = mix['think_s']
        self._think = rng.permutation(
            quantile_uniform(think['min'], think['max'], pool))
        self._system = [
            _ids(_rng(seed, 100 + t), int(mix['system_prompt']), vocab)
            for t in range(self.tenants)]

    def turns(self, k: int) -> List[Request]:
        """Session ``k``'s turns in order. A turn whose padded prefill
        would overhang ``max_len`` (or whose answer would not fit) ends
        the session early: the context is full."""
        rng = _rng(self.seed, 1000 + k)
        pool = len(self._msg)
        prompt = list(self._system[k % self.tenants])
        out: List[Request] = []
        prev_len = 0
        for t in range(self.n_turns):
            j = (k * self.n_turns + t) % pool
            msg, ans = int(self._msg[j]), int(self._ans[j])
            prompt = prompt + _ids(rng, msg, self.vocab)
            covered = (prev_len // self.block) * self.block
            if (covered + pad_width(len(prompt) - covered, self.pad_lo)
                    > self.max_len or len(prompt) + ans > self.max_len):
                break
            out.append(Request(rid=k * self.n_turns + t, prompt=list(prompt),
                               max_new=ans, session=k, turn=t,
                               think_s=float(self._think[j])))
            prev_len = len(prompt)
            # Scripted history: the reply as a later prompt carries it.
            prompt = prompt + _ids(rng, ans, self.vocab)
        return out


# -- backlog (closed loop) ---------------------------------------------------


def backlog_requests(mix: Dict, vocab: int, seed: int) -> Iterator[Request]:
    """An endless stream for the backlog loop: a stratified pool of
    ``pool`` (prompt, answer) pairs permuted by the seed, cycled."""
    pool = int(mix['pool'])
    rng = _rng(seed, 21)
    prompts = rng.permutation(quantile_lengths(mix['prompt'], pool))
    answers = rng.permutation(quantile_lengths(mix['answer'], pool))
    rid = 0
    while True:
        j = rid % pool
        yield Request(rid=rid,
                      prompt=_ids(_rng(seed, 5000 + rid), prompts[j], vocab),
                      max_new=int(answers[j]))
        rid += 1


# -- training ----------------------------------------------------------------


def train_batch(mix: Dict, vocab: int, seed: int, step: int) -> np.ndarray:
    """Batch ``step`` of a training mix: ``[batch, seq_len]`` int32,
    each row packed from seeded documents whose lengths are the
    stratified quantiles of ``mix['document']`` (permuted per row), a
    separator id between them. Rows all differ; a function of
    (seed, step) alone."""
    b, s = int(mix['batch']), int(mix['seq_len'])
    sep = int(mix.get('separator_id', 0))
    docs_per_row = int(mix.get('documents_per_row', 16))
    lengths = quantile_lengths(mix['document'], docs_per_row)
    out = np.zeros((b, s), np.int32)
    for r in range(b):
        rng = _rng(seed, 9000 + step * 131 + r)
        row: List[int] = []
        for n in rng.permutation(lengths):
            row += _ids(rng, int(n), vocab) + [sep]
            if len(row) >= s:
                break
        while len(row) < s:  # the multiset fell short: one more document
            row += _ids(rng, int(lengths[-1]), vocab) + [sep]
        out[r] = row[:s]
    return out
