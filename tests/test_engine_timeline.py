"""What the engine records of itself (models/engine.py): each request's
``RequestTimeline`` on the future it returns, and the loop's spans
opened through ``observability.profiler.span``."""
import contextlib
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu.models import engine as engine_lib
from skypilot_tpu.models import generate, llama
from skypilot_tpu.observability import profiler

STAMPS = ('submit', 'admit', 'prefill', 'first', 'last')


@pytest.fixture(scope='module')
def tiny():
    cfg = llama.TINY
    return cfg, llama.init_params(jax.random.PRNGKey(0), cfg)


def _mk(params, cfg, **kw):
    kw.setdefault('slots', 4)
    kw.setdefault('max_len', 64)
    kw.setdefault('chunk_steps', 4)
    return engine_lib.ContinuousEngine(params, cfg, **kw)


def _row(n, salt=0):
    return [(7 * i + 11 * salt) % 250 + 1 for i in range(n)]


def _whole(tl):
    """Complete, ordered, and the three waits telescope."""
    at = [getattr(tl, s) for s in STAMPS]
    assert None not in at, dict(zip(STAMPS, at))
    assert at == sorted(at), dict(zip(STAMPS, at))
    waits = (tl.admit - tl.submit) + (tl.prefill - tl.admit) + (
        tl.first - tl.prefill)
    assert abs(waits - (tl.first - tl.submit)) < 1e-6
    phases = tl.phases()
    assert [p[0] for p in phases] == ['engine.queue', 'engine.prep',
                                      'engine.first_wait', 'engine.decode']
    assert phases[0][1] == tl.submit and phases[-1][2] == tl.last
    assert all(a[2] == b[1] for a, b in zip(phases, phases[1:]))


def _grouped(params, cfg):
    eng = _mk(params, cfg, prefill_batch=2)
    try:
        # Two at once: whichever way the loop wakes, one group of two or
        # two groups of one.
        futs = [eng.submit(_row(5, s), 6) for s in range(2)]
        for f in futs:
            f.result(timeout=120)
        assert {f.timeline.path for f in futs} == {'group'}
        assert all(f.timeline.group in (1, 2) for f in futs)
        return [f.timeline for f in futs]
    finally:
        eng.stop()


def _shared(params, cfg):
    eng = _mk(params, cfg, kv_block=16, max_len=96,
              prefix_share=True, kv_tiers=False)
    try:
        head = _row(32)
        first = eng.submit(head + _row(4, 1), 4)
        first.result(timeout=120)
        assert first.timeline.path == 'group'
        assert first.timeline.saved_tokens == 0
        second = eng.submit(head + _row(6, 2), 4)
        second.result(timeout=120)
        assert second.timeline.path == 'shared'
        assert second.timeline.group == 1
        assert second.timeline.saved_tokens == 32
        return [first.timeline, second.timeline]
    finally:
        eng.stop()


def _long(params, cfg):
    eng = _mk(params, cfg, prefill_chunk=8)
    try:
        fut = eng.submit(_row(30), 6)
        fut.result(timeout=120)
        assert fut.timeline.path == 'long'
        return [fut.timeline]
    finally:
        eng.stop()


def _one_token(params, cfg):
    eng = _mk(params, cfg)
    try:
        fut = eng.submit(_row(5), 1)
        assert len(fut.result(timeout=120)) == 1
        return [fut.timeline]
    finally:
        eng.stop()


def _first_token_eos(params, cfg):
    row = _row(5)
    solo = generate.generate(params, cfg, jnp.asarray([row], jnp.int32),
                             max_new_tokens=1, max_len=64)
    eos = int(np.asarray(solo[0])[0])
    eng = _mk(params, cfg)
    try:
        fut = eng.submit(row, 6, eos=eos)
        assert fut.result(timeout=120) == [eos]
        return [fut.timeline]
    finally:
        eng.stop()


def _export_then_import(params, cfg):
    pre = _mk(params, cfg, role='prefill')
    dec = _mk(params, cfg, role='decode')
    try:
        row = _row(13)
        out = pre.submit_prefill(row, 8)
        h = out.result(timeout=120)
        assert out.timeline.path == 'group'
        got = dec.submit_import(row, 8, h.first, k=h.k,
                                v=h.v)
        assert len(got.result(timeout=120)) == 8
        assert got.timeline.path == 'import'
        assert got.timeline.prefill == got.timeline.admit
        return [out.timeline, got.timeline]
    finally:
        pre.stop()
        dec.stop()


@pytest.mark.parametrize('drive', [_grouped, _shared, _long, _one_token,
                                   _first_token_eos, _export_then_import],
                         ids=lambda f: f.__name__.strip('_'))
def test_a_finished_request_has_a_whole_timeline(tiny, drive):
    cfg, params = tiny
    for tl in drive(params, cfg):
        _whole(tl)


def test_the_callback_may_read_first_as_soon_as_it_is_called(tiny):
    cfg, params = tiny
    eng = _mk(params, cfg)
    seen, holder, ready = [], {}, threading.Event()

    def on_tokens(new):
        ready.wait(10)      # until submit() has handed the future back
        tl = holder['fut'].timeline
        seen.append((tl.first, tl.last, time.perf_counter()))

    try:
        holder['fut'] = fut = eng.submit(_row(5), 6, on_tokens=on_tokens)
        ready.set()
        fut.result(timeout=120)
    finally:
        eng.stop()
    first, last, now = seen[0]
    assert first is not None and first <= now and last is None
    assert seen[-1][1] == fut.timeline.last    # stamped before callbacks


def test_a_request_that_fails_keeps_the_stamps_it_had_reached(tiny):
    cfg, params = tiny
    eng = _mk(params, cfg)

    def broken(reqs, slots):
        raise RuntimeError('prefill dispatch failed')

    eng._prefill_group = broken
    try:
        fut = eng.submit(_row(5), 6)
        with pytest.raises(RuntimeError):
            fut.result(timeout=120)
    finally:
        eng.stop()
    tl = fut.timeline
    assert tl.submit <= tl.admit and tl.path == 'group'
    assert (tl.prefill, tl.first, tl.last) == (None, None, None)
    assert [p[0] for p in tl.phases()] == ['engine.queue']


class _Recorder:
    """Stands in for ``profiler.span``: every span with its parent (the
    span open on the same thread when it opened) and a proof that spans
    close in the reverse of the order they opened in."""

    def __init__(self):
        self.local = threading.local()
        self.spans = []       # (name, parent name or None, thread)
        self.crossed = []

    @contextlib.contextmanager
    def __call__(self, name):
        stack = self.local.__dict__.setdefault('stack', [])
        self.spans.append((name, stack[-1] if stack else None,
                           threading.current_thread().name))
        stack.append(name)
        try:
            yield
        finally:
            if stack.pop() != name:
                self.crossed.append(name)


def test_the_loop_opens_its_spans_properly_nested(tiny, monkeypatch):
    cfg, params = tiny
    rec = _Recorder()
    monkeypatch.setattr(profiler, 'span', rec)
    eng = _mk(params, cfg, kv_block=16, max_len=96,
              prefix_share=True, kv_tiers=False, prefill_chunk=40)
    try:
        head = _row(32)
        eng.submit(head + _row(4, 1), 6,
                   on_tokens=lambda new: None).result(timeout=120)
        eng.submit(head + _row(6, 2), 6).result(timeout=120)   # shared
        eng.submit(_row(50, 3), 6).result(timeout=120)         # chunked
        time.sleep(0.05)        # let the loop go idle once more
    finally:
        eng.stop()
    assert not rec.crossed
    assert {t for _, _, t in rec.spans} == {'skytpu-decode-engine'}
    parents = {}
    for name, parent, _ in rec.spans:
        parents.setdefault(name, set()).add(parent)
    assert set(parents) == {
        'engine.admit', 'engine.admit_shared', 'engine.prefill_group',
        'engine.dispatch_chunk', 'engine.drain_firsts',
        'engine.retire_chunk', 'engine.advance_prefill',
        'engine.admit_imports', 'engine.wait_chunk', 'engine.wait_firsts',
        'engine.callbacks', 'engine.idle'}
    top = {None}
    for name in ('engine.admit', 'engine.admit_imports',
                 'engine.advance_prefill', 'engine.dispatch_chunk',
                 'engine.idle'):
        assert parents[name] == top, name
    assert parents['engine.prefill_group'] == {'engine.admit'}
    assert parents['engine.admit_shared'] == {'engine.admit'}
    assert parents['engine.wait_chunk'] == {'engine.retire_chunk'}
    assert parents['engine.retire_chunk'] == top
    # Firsts are drained at a chunk's retirement, and from the loop
    # itself once nothing decodes.
    assert parents['engine.drain_firsts'] <= {'engine.retire_chunk', None}
    assert 'engine.retire_chunk' in parents['engine.drain_firsts']
    assert parents['engine.wait_firsts'] <= {'engine.drain_firsts',
                                             'engine.advance_prefill'}
    assert parents['engine.callbacks'] <= {
        'engine.retire_chunk', 'engine.drain_firsts',
        'engine.advance_prefill', 'engine.admit_imports'}


def test_span_is_the_profilers_annotation():
    with profiler.span('engine.test') as s:
        assert isinstance(s, jax.profiler.TraceAnnotation)
