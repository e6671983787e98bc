"""The hold of the pipelined decode chunk (models/engine.py,
``ContinuousEngine._hold_chunk``): with a chunk in flight the loop
sleeps BEFORE it dispatches the next one, until the one in flight is
nearly done, and a submit() that ends the sleep is admitted at once, so
its prefill runs behind one chunk and not two. Pinned here: an arrival
inside a hold is admitted before the next dispatch and counted; the
tokens are the serial engine's and ``generate``'s; the loop does not
hold where it could not help or cannot tell; the estimate errs early."""
import dataclasses
import json
import os
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_engine_timeline import _Recorder   # beside this file

from benchmarks import manifest, weights
from benchmarks.readers import engine_stat
from skypilot_tpu.models import engine as engine_lib
from skypilot_tpu.models import generate, llama
from skypilot_tpu.observability import blackbox, profiler

DATA = os.path.join(os.path.dirname(__file__), 'benchmarks', 'data')


@pytest.fixture(scope='module')
def tiny():
    cfg = llama.TINY
    return cfg, llama.init_params(jax.random.PRNGKey(0), cfg)


@pytest.fixture(scope='module')
def latent_state():
    """The tiny Kimi-Linear of tests/test_kda.py: KDA layers with a
    state a slot beside one NoPE MLA layer's latent pool, float32."""
    with open(os.path.join(DATA, 'tiny_kda_mla_moe_config.json')) as f:
        cfg = json.load(f)
    fam = manifest.family(cfg)
    params = jax.tree.map(lambda x: x.astype(jnp.float32),
                          weights.make_params(cfg, 5))
    return dataclasses.replace(fam.program_config(cfg),
                               dtype=jnp.float32), params


@pytest.fixture()
def short_lead(monkeypatch):
    """A tiny model's chunk is a few ms on this CPU: the lead is its
    last step's time alone, and an admission may start late in a hold."""
    monkeypatch.setattr(engine_lib, '_HOLD_LEAD_S', 0.0)
    monkeypatch.setattr(engine_lib, '_HOLD_ADMIT_S', 0.0005)


def _row(n, salt=0, vocab=250):
    return [(7 * i + 11 * salt) % vocab + 1 for i in range(n)]


def _solo(params, cfg, row, n, max_len):
    out = generate.generate(params, cfg, jnp.asarray([row], jnp.int32),
                            max_new_tokens=n, max_len=max_len)
    return np.asarray(out[0]).tolist()


def _until(cond, limit_s=120.0):
    end = time.perf_counter() + limit_s
    while not cond():
        assert time.perf_counter() < end, 'timed out'
        time.sleep(0.002)


def _holding(eng, n=1):
    """Wait until the loop has held ``n`` chunks back: a chunk has been
    seen at both ends, and a row is live."""
    _until(lambda: eng.stats()['active_slots'])

    def held():
        st = eng.stats()
        assert st['active_slots'], 'the carrier ended before a hold'
        return st['pipeline']['holds'] >= n
    _until(held)


# -- an arrival inside a hold ------------------------------------------------


def test_an_arrival_inside_a_hold_is_admitted_before_the_next_dispatch(
        tiny, short_lead):
    cfg, params = tiny
    eng = engine_lib.ContinuousEngine(params, cfg, slots=4, max_len=512,
                                      chunk_steps=32)
    eng.start()
    try:
        eng.submit(_row(3), 40).result(timeout=120)     # compiles
        carrier = eng.submit(_row(3), 500)
        _holding(eng, 2)
        early, rows = 0, []
        # An arrival is early unless it falls into a chunk's last step
        # or its retirement: a few tries make one all but certain.
        for i in range(8):
            blackbox.reset()
            rows.append(_row(5 + i, salt=i + 1))
            fut = eng.submit(rows[-1], 3)
            assert fut.result(timeout=120) == _solo(params, cfg, rows[-1],
                                                    3, 512)
            early = eng.stats()['pipeline']['early_admits']
            if early:
                break
            time.sleep(0.011)
        assert early == 1
        st = eng.stats()
        assert 0 < st['early_admit_share'] <= 100.0
        assert st['early_admit_share'] == pytest.approx(
            100.0 / (len(rows) + 2), abs=0.01)
        assert st['pipeline']['hold_ms'] > 0
        # The ring since the submit: the hold it ended, its admission,
        # then (the rest of the hold and) the next chunk's dispatch.
        names = [(e['name'], e.get('attrs', {})) for e in blackbox.events()
                 if e['name'] in ('engine.hold', 'engine.admit',
                                  'engine.dispatch')]
        at = [i for i, (n, a) in enumerate(names) if n == 'engine.admit']
        assert len(at) == 1 and names[at[0]][1]['prompt_len'] == len(
            rows[-1])
        before = names[:at[0]]
        assert before and before[-1][0] == 'engine.hold' and before[-1][1][
            'woke'] is True, names
        assert 'engine.dispatch' in [n for n, _ in names[at[0]:]], names
        carrier.result(timeout=120)
    finally:
        eng.stop()


# -- the tokens are the serial engine's --------------------------------------


def _llama_case(tiny):
    cfg, params = tiny
    kw = dict(slots=4, max_len=256, chunk_steps=16, kv_block=16)
    rows = [_row(3), _row(5, 1), _row(9, 2), _row(4, 3), _row(17, 4),
            _row(6, 5)]
    return cfg, params, kw, rows, [200, 7, 9, 6, 12, 5]


def _latent_state_case(latent_state):
    """Trimmed chunks (a chunk ends with its first row to finish) and a
    prompt that goes in pieces of 16 between them."""
    cfg, params = latent_state
    kw = dict(slots=3, max_len=160, kv_blocks=31, kv_block=16,
              prefill_batch=2, chunk_steps=8, prefix_share=False,
              kv_tiers=False, kv_quantize=False, prefill_chunk=16)
    rows = [_row(20), _row(70, 1), _row(13, 2), _row(40, 3), _row(7, 4)]
    return cfg, params, kw, rows, [120, 7, 9, 6, 11]


@pytest.mark.parametrize('family', ['paged_llama', 'latent_state_pieces'])
def test_greedy_streams_with_the_hold_engaged_are_the_serial_engines(
        family, request, short_lead):
    """The first request keeps chunks in flight; the others arrive while
    the loop holds, one by one. Every stream equals the serial engine's
    (no pipeline, so no hold) and ``generate``'s."""
    if family == 'paged_llama':
        cfg, params, kw, rows, news = _llama_case(
            request.getfixturevalue('tiny'))
    else:
        cfg, params, kw, rows, news = _latent_state_case(
            request.getfixturevalue('latent_state'))
    got = {}
    # The serial engine first: it compiles what both use, so that no
    # admission inside a hold outlasts its chunk by a compile.
    for pipe in (False, True):
        eng = engine_lib.ContinuousEngine(params, cfg, pipeline=pipe, **kw)
        eng.start()
        try:
            assert eng._trim_chunks == (family == 'latent_state_pieces')
            futs = [eng.submit(rows[0], news[0])]
            if pipe:
                _holding(eng)
            else:
                _until(lambda: eng.stats()['active_slots'])
            for row, n in zip(rows[1:], news[1:]):
                futs.append(eng.submit(row, n))
                time.sleep(0.004)
            got[pipe] = [f.result(timeout=300) for f in futs]
            pl = eng.stats()['pipeline']
            if pipe:
                assert pl['holds'] >= 2 and pl['hold_ms'] > 0
                assert pl['early_admits'] >= 1, pl
            else:
                assert pl['holds'] == pl['early_admits'] == 0
        finally:
            eng.stop()
    assert got[True] == got[False]
    for row, n, out in zip(rows, news, got[True]):
        assert out == _solo(params, cfg, row, n, kw['max_len']), len(row)


# -- where the loop does not hold --------------------------------------------


@pytest.mark.parametrize('case', ['serial', 'draft', 'every_slot_taken'])
def test_no_hold_where_it_could_not_help(tiny, short_lead, case):
    """Depth 0 and a draft keep nothing in flight; with every slot
    taken an arrival could not be admitted anyway, and the loop is the
    loop without the hold (the same engine with a slot to spare holds:
    the test above)."""
    cfg, params = tiny
    kw = dict(slots=2, max_len=256, chunk_steps=32)
    if case == 'serial':
        kw['pipeline'] = False
    elif case == 'draft':
        kw.update(draft_params=params, draft_cfg=cfg)
    else:
        kw['slots'] = 1
    eng = engine_lib.ContinuousEngine(params, cfg, **kw)
    eng.start()
    try:
        row = _row(4)
        assert eng.submit(row, 200).result(timeout=300) == _solo(
            params, cfg, row, 200, 256)
        pl = eng.stats()['pipeline']
        assert pl['dispatches'] >= 6 or case == 'draft'
        assert pl['holds'] == 0 and pl['hold_ms'] == 0.0
        assert pl['hold_overruns'] == 0
        if case == 'every_slot_taken':
            # ... though it had learned a step time, and would have held
            assert pl['pipeline_depth'] == 1 and len(eng._step_s)
    finally:
        eng.stop()


def _flight(**kw):
    return engine_lib._Inflight(reqs=[], toks=None, steps=8, **kw)


def test_no_hold_with_nothing_in_flight_or_nothing_known(tiny):
    cfg, params = tiny
    eng = engine_lib.ContinuousEngine(params, cfg, slots=2, max_len=64)
    now = time.perf_counter()
    assert eng._inflight is None and eng._hold_chunk() is False
    # In flight, begun just now, but no chunk has been timed yet.
    eng._inflight = _flight(start=now, exact=True)
    assert eng._hold_chunk() is False
    # Timed, but the retirement before it did not block: the host is
    # behind the device and cannot tell when this chunk began.
    eng._step_s.append(0.5)
    eng._note_flight_end(_flight(), now, blocked=False)
    assert eng._inflight.start is None and eng._hold_chunk() is False
    assert eng.stats()['pipeline']['holds'] == 0
    eng._inflight = None


def test_what_a_retirement_teaches(tiny):
    """A chunk seen at both ends with nothing queued behind it teaches
    the step time, the least of the last three is the estimate; a hold
    that outlasted its chunk forgets it."""
    cfg, params = tiny
    eng = engine_lib.ContinuousEngine(params, cfg, slots=2, max_len=64)
    nxt = eng._inflight = _flight()
    eng._note_flight_end(_flight(start=10.0, exact=True), 10.4, True)
    assert list(eng._step_s) == [pytest.approx(0.05)]
    assert nxt.start == 10.4 and nxt.exact
    # not seen to begin; trimmed; a prefill behind it; not seen to end
    eng._note_flight_end(_flight(start=10.0), 10.2, True)
    eng._note_flight_end(engine_lib._Inflight(
        reqs=[], toks=None, steps=2, start=10.0, exact=True), 10.2, True)
    eng._note_flight_end(_flight(start=10.0, exact=True, followed=True),
                         10.2, True)
    eng._note_flight_end(_flight(start=10.0, exact=True), 10.2, False)
    assert len(eng._step_s) == 1 and nxt.start is None
    for end in (10.8, 10.24, 10.32, 10.4):
        eng._note_flight_end(_flight(start=10.0, exact=True), end, True)
    assert min(eng._step_s) == pytest.approx(0.03) and len(eng._step_s) == 3
    assert eng.stats()['pipeline']['hold_overruns'] == 0
    eng._note_flight_end(_flight(start=10.0, exact=True, held=True), 10.4,
                         False)
    assert not eng._step_s
    assert eng.stats()['pipeline']['hold_overruns'] == 1
    eng._inflight = None


# -- the estimate, alone -----------------------------------------------------


@pytest.mark.parametrize('step_s', [0.0002, 0.001, 0.0058, 0.0113, 0.05])
@pytest.mark.parametrize('steps', [1, 3, 8])
def test_the_dispatch_is_never_past_the_chunks_end_less_the_lead(step_s,
                                                                 steps):
    start = 1000.0
    at, admit_until = engine_lib.hold_plan(start, step_s, steps)
    assert at <= start + steps * step_s - engine_lib._HOLD_LEAD_S
    assert at <= start + (steps - 1) * step_s     # the last step's time
    # no admission is started after the dispatch is due, nor with less
    # than an admission's host time left of the chunk
    assert admit_until <= at
    assert admit_until <= start + steps * step_s - engine_lib._HOLD_ADMIT_S
    # scales with the chunk's own step count (a trimmed chunk is short)
    assert at - engine_lib.hold_plan(start, step_s, 8)[0] == pytest.approx(
        (steps - 8) * step_s)
    assert engine_lib.hold_plan(None, step_s, steps) is None
    assert engine_lib.hold_plan(start, None, steps) is None


# -- spans -------------------------------------------------------------------


def test_a_hold_sleeps_under_engine_idle_at_the_top_of_the_loop(
        tiny, short_lead, monkeypatch):
    """No span of its own: ``engine.idle`` is what the readers take for
    waiting (benchmarks/readers/host_spans.py), and it stays a top-level
    span, opened between a chunk's dispatch and the next."""
    cfg, params = tiny
    rec = _Recorder()
    monkeypatch.setattr(profiler, 'span', rec)
    eng = engine_lib.ContinuousEngine(params, cfg, slots=2, max_len=512,
                                      chunk_steps=32)
    eng.start()
    try:
        eng.submit(_row(4), 40).result(timeout=120)     # compiles
        fut = eng.submit(_row(4), 480)
        _holding(eng, 3)
        idles = sum(n == 'engine.idle' for n, _, _ in rec.spans)
        assert idles >= 3          # and nothing has gone idle yet
        assert eng.stats()['active_slots'] == 1
        fut.result(timeout=120)
    finally:
        eng.stop()
    assert not rec.crossed
    assert {p for n, p, _ in rec.spans if n == 'engine.idle'} == {None}
    assert not any(n.startswith('engine.hold') for n, _, _ in rec.spans)
    order = [n for n, _, _ in rec.spans
             if n in ('engine.idle', 'engine.dispatch_chunk')]
    held = sum(a == 'engine.dispatch_chunk' and b == 'engine.idle'
               for a, b in zip(order, order[1:]))
    assert held >= eng.stats()['pipeline']['holds'] >= 3


# -- the metric --------------------------------------------------------------


def test_the_early_admit_share_is_read_where_the_program_counts_it(tiny):
    m = manifest.metric_file('engine.early_admit_share.ttft')
    entry = [e for e in manifest.benchmark()['per_layer']
             if e['name'] == m['name']]
    assert len(entry) == 1 and entry[0]['workloads'] == ['chat-steady']
    for key in ('unit', 'better', 'source', 'layer', 'moves'):
        assert entry[0][key] == m[key]
    assert (m['unit'], m['better'], m['moves']) == ('%', 'higher',
                                                    'ttft_p90_ms')
    read = manifest.reader(m['reader'])
    assert read is engine_stat.read
    cfg, params = tiny
    stats = engine_lib.ContinuousEngine(params, cfg, slots=2,
                                        max_len=64).stats()
    assert read(types.SimpleNamespace(stats1=stats), **m['args']) == 0.0
    stats['early_admit_share'] = 72.5
    assert read(types.SimpleNamespace(stats1=stats), **m['args']) == 72.5
    # a program without the hold (the parent commit) has no such key
    del stats['early_admit_share']
    assert read(types.SimpleNamespace(stats1=stats), **m['args']) is None
    assert read(types.SimpleNamespace(stats1=None), **m['args']) is None
