"""Black-box flight recorder (observability/blackbox.py).

Pinned contracts: the ring is bounded under sustained recording; dumps
are atomic (torn files invisible to the list path) and rotated; bundles
carry events + thread stacks + open trace spans + declared env flags
with secrets masked; a deterministic injected ENGINE failure produces a
committed bundle holding the triggering event and the preceding ring
(slow tier — it compiles the tiny engine); the trainer's SIGTERM path
orders emergency-persist BEFORE the bundle write and both before
exit 143; disabling via SKYTPU_BLACKBOX=0 turns recording and dumping
into no-ops; and bundles never contain request token ids or prompt
text (the redaction contract docs/operations.md promises).
"""
import json
import os

import pytest

from skypilot_tpu.observability import blackbox
from skypilot_tpu.observability import trace as trace_lib


@pytest.fixture(autouse=True)
def _isolated_recorder(tmp_path, monkeypatch):
    monkeypatch.setenv('SKYTPU_BLACKBOX_DIR', str(tmp_path / 'spool'))
    monkeypatch.delenv('SKYTPU_BLACKBOX', raising=False)
    monkeypatch.delenv('SKYTPU_BLACKBOX_RING', raising=False)
    monkeypatch.delenv('SKYTPU_BLACKBOX_KEEP', raising=False)
    blackbox.reset()
    blackbox.register_health_provider(None)
    # A span another file's test left open in this worker would ride
    # into every bundle's open traces.
    trace_lib.reset()
    yield
    blackbox.reset()
    blackbox.register_health_provider(None)


def _spool(tmp_path):
    return tmp_path / 'spool'


# -- ring --------------------------------------------------------------------


def test_ring_overwrite_keeps_bounded_memory(monkeypatch):
    monkeypatch.setenv('SKYTPU_BLACKBOX_RING', '64')
    for i in range(10_000):
        blackbox.record('engine.dispatch', active=i)
    evs = blackbox.events()
    assert len(evs) == 64
    # Oldest events were overwritten: the ring holds the NEWEST 64.
    assert evs[-1]['attrs']['active'] == 9_999
    assert evs[0]['attrs']['active'] == 9_936


def test_disabled_records_and_dumps_nothing(tmp_path, monkeypatch):
    monkeypatch.setenv('SKYTPU_BLACKBOX', '0')
    blackbox.record('engine.dispatch', active=1)
    assert blackbox.events() == []
    assert blackbox.dump('manual') is None
    assert not _spool(tmp_path).exists()


# -- bundle anatomy ----------------------------------------------------------


def test_dump_bundle_contents(tmp_path, monkeypatch):
    monkeypatch.setenv('SKYTPU_API_TOKEN', 'super-secret-token')
    monkeypatch.setenv('SKYTPU_LLM_SLOTS', '8')
    blackbox.set_process_label('pytest')
    blackbox.register_health_provider(
        lambda: {'status': 'ok', 'queue': {'depth_total': 3}})
    blackbox.record('engine.admit', n=2, shared=False)
    blackbox.record('engine.retire', emitted=4, max_new=4)

    from skypilot_tpu.observability import trace as trace_lib
    # Roots this test does not own: another file's server thread can
    # hold a live one in the same xdist worker (``trace_lib.reset()``
    # cannot close it). They are left out of the comparison below.
    foreign = {t['trace_id'] for t in trace_lib.open_spans()}
    with trace_lib.start_trace('unit.open_span'):
        path = blackbox.dump('manual', reason='unit test')
    assert path is not None and os.path.basename(path).startswith(
        'incident-')
    with open(path, encoding='utf-8') as f:
        b = json.load(f)
    assert b['trigger'] == 'manual' and b['proc'] == 'pytest'
    assert [e['name'] for e in b['events']] == ['engine.admit',
                                                'engine.retire']
    assert all('mono' in e and 'ts' in e for e in b['events'])
    # The last /health snapshot rides along.
    assert b['health'] == {'status': 'ok', 'queue': {'depth_total': 3}}
    # Open (unfinished) trace spans are frozen in.
    assert [t['name'] for t in b['traces']['open']
            if t['trace_id'] not in foreign] == ['unit.open_span']
    # faulthandler all-thread stacks.
    assert 'Current thread' in b['stacks'] or 'Thread 0x' in b['stacks']
    # Declared env flags present, secrets masked to presence.
    assert b['env_flags']['SKYTPU_LLM_SLOTS'] == '8'
    assert b['env_flags']['SKYTPU_API_TOKEN'] == '<redacted>'
    assert 'super-secret-token' not in json.dumps(b)
    assert blackbox.dump_counts() == {'manual': 1}


def test_unknown_trigger_clamped_to_manual():
    path = blackbox.dump('totally-made-up')
    with open(path, encoding='utf-8') as f:
        assert json.load(f)['trigger'] == 'manual'


# -- spool discipline --------------------------------------------------------


def test_torn_and_foreign_files_invisible_to_list(tmp_path):
    blackbox.record('engine.dispatch', active=1)
    good = blackbox.dump('manual')
    spool = _spool(tmp_path)
    # A torn write that somehow acquired the .json suffix: half a JSON
    # object (crash mid-copy, partial scp).
    (spool / 'incident-0000000000001-1-manual.json').write_text(
        '{"version": 1, "events": [', encoding='utf-8')
    # An in-progress atomic write (dot-tmp) and an unrelated file.
    (spool / '.incident-0000000000002-1-manual.json.tmp').write_text(
        '{}', encoding='utf-8')
    (spool / 'notes.txt').write_text('not a bundle', encoding='utf-8')
    # Valid JSON that is not a bundle (no trigger).
    (spool / 'incident-0000000000003-1-manual.json').write_text(
        '[1, 2, 3]', encoding='utf-8')
    listed = blackbox.list_bundles()
    assert [b['file'] for b in listed] == [os.path.basename(good)]
    # read_bundle rejects traversal and non-bundle names outright.
    assert blackbox.read_bundle('../etc/passwd') is None
    assert blackbox.read_bundle('notes.txt') is None


def test_rotation_keeps_newest(monkeypatch):
    monkeypatch.setenv('SKYTPU_BLACKBOX_KEEP', '3')
    paths = [blackbox.dump('manual', reason=str(i)) for i in range(5)]
    listed = blackbox.list_bundles()
    assert len(listed) == 3
    kept = {b['file'] for b in listed}
    assert os.path.basename(paths[-1]) in kept
    assert os.path.basename(paths[0]) not in kept


def test_debug_payload_dump_now_round_trip():
    blackbox.record('engine.dispatch', active=2)
    out = blackbox.debug_payload({'dump': '1', 'trigger': 'manual',
                                  'reason': 'operator poke'})
    assert out['dumped'] is not None
    assert out['bundle']['reason'] == 'operator poke'
    assert out['bundle']['events'][-1]['name'] == 'engine.dispatch'
    assert len(out['bundles']) == 1
    # Plain list call sees the committed bundle.
    again = blackbox.debug_payload({})
    assert [b['file'] for b in again['bundles']] == \
        [os.path.basename(out['dumped'])]


# -- trigger paths -----------------------------------------------------------


@pytest.mark.slow
def test_engine_failure_dumps_bundle_with_ring(tmp_path, monkeypatch):
    """A deterministic injected engine failure commits a bundle holding
    the triggering engine.fail event, the last >= 50 ring events of the
    healthy traffic that preceded it, thread stacks — and none of the
    request token ids (redaction contract)."""
    import jax

    from skypilot_tpu.models import engine as engine_lib
    from skypilot_tpu.models import llama
    cfg = llama.TINY
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    eng = engine_lib.ContinuousEngine(params, cfg, slots=2, max_len=64,
                                      chunk_steps=2)
    eng.start()
    sentinel_row = [97, 89, 83]
    try:
        # Healthy traffic first, so the ring holds real history
        # (admit + dispatch + retire edges) when the fault lands.
        for i in range(14):
            fut = eng.submit(list(sentinel_row), 6, 0.0)
            fut.result(timeout=120)
        assert len(blackbox.events()) >= 50

        def _boom():
            raise RuntimeError('injected-fault')

        monkeypatch.setattr(eng, '_run_chunk', _boom)
        doomed = eng.submit(list(sentinel_row), 6, 0.0)
        with pytest.raises(Exception, match='injected-fault'):
            doomed.result(timeout=120)
    finally:
        eng.stop()
    bundles = [b for b in blackbox.list_bundles()
               if b['trigger'] == 'engine_failure']
    assert bundles, blackbox.list_bundles()
    b = blackbox.read_bundle(bundles[0]['file'])
    assert b['reason'].startswith("RuntimeError('injected-fault'")
    names = [e['name'] for e in b['events']]
    fails = [e for e in b['events'] if e['name'] == 'engine.fail']
    assert fails and 'injected-fault' in fails[-1]['attrs']['cause']
    # >=: _fail_everything's doomed list deliberately tolerates dupes
    # (a request can sit in a slot AND the in-flight chunk snapshot).
    assert fails[-1]['attrs']['doomed'] >= 1
    assert len(b['events']) >= 50
    assert {'engine.admit', 'engine.dispatch', 'engine.retire'} <= \
        set(names)
    assert 'Thread 0x' in b['stacks'] or 'Current thread' in b['stacks']
    # Redaction: the prompt ids never enter the bundle in any form.
    text = json.dumps(b)
    assert '97, 89, 83' not in text and '"tokens"' not in text


def test_sigterm_orders_persist_before_bundle(tmp_path):
    """The trainer's preemption handler: emergency-persist FIRST (the
    bundle must not delay durability), bundle committed BEFORE the
    SystemExit(143) escapes."""
    from skypilot_tpu.train import run as run_mod
    spool = _spool(tmp_path)
    order = []

    class FakeMgr:
        def emergency_persist(self):
            bundles = (sorted(spool.glob('incident-*.json'))
                       if spool.exists() else [])
            order.append(('persist', len(bundles)))
            return 7

    handler = run_mod.make_sigterm_handler(FakeMgr())
    with pytest.raises(SystemExit) as exc:
        handler(15, None)
    assert exc.value.code == 143
    # Persist ran exactly once, and at that moment NO bundle existed —
    # the dump cannot have delayed it.
    assert order == [('persist', 0)]
    bundles = blackbox.list_bundles()
    assert len(bundles) == 1 and bundles[0]['trigger'] == 'sigterm'


# -- registry ----------------------------------------------------------------


def test_event_registry_shape():
    assert len(blackbox.EVENT_NAMES) == len(blackbox.EVENTS)
    for ev in blackbox.EVENTS:
        assert ev.doc, f'{ev.name} needs a doc line'
        assert ev.name == ev.name.lower()
    for trig in blackbox.TRIGGERS:
        assert trig.replace('_', '').isalpha()
