"""Disaggregated prefill/decode serving (serve/disagg.py).

Pins the subsystem's contracts: greedy output byte-identical colocated
vs disaggregated (two block lengths, with and without
prefix-share-negotiated transfers), corrupt/truncated handoff payloads
rejected BEFORE any device install with the LB falling back to
colocated serving, decode-pool admission backpressure on imported
blocks, and the LB re-routing (resuming the stream on a surviving
replica) when the decode replica dies mid-stream.
"""
import asyncio
import json
import os
import pathlib
import sys
import threading
import time

import jax
import pytest
import requests as requests_lib

sys.path.insert(0, str(pathlib.Path(__file__).parents[1]))

from skypilot_tpu.models import llama  # noqa: E402
from skypilot_tpu.models.engine import ContinuousEngine  # noqa: E402
from skypilot_tpu.serve import disagg  # noqa: E402


@pytest.fixture(scope='module')
def tiny_params():
    cfg = llama.TINY
    return cfg, llama.init_params(jax.random.PRNGKey(0), cfg)


def _engine(tiny_params, role='colocated', **kw):
    cfg, params = tiny_params
    kw.setdefault('slots', 4)
    kw.setdefault('max_len', 96)
    return ContinuousEngine(params, cfg, role=role, **kw)


def _row(n, salt=0):
    return [(7 * i + 11 * salt) % 250 + 1 for i in range(n)]


def _handoff_bytes(pre, row, max_new, skip_blocks=0, **hkw):
    h = pre.submit_prefill(row, max_new, **hkw).result(timeout=300)
    header = disagg.build_header(h, model='tiny', kv_cache='bf16',
                                 skip_blocks=skip_blocks)
    return disagg.serialize_bytes(h, header)


def _import_tokens(dec, data, max_len=96):
    header, arrays = disagg.parse(data)
    disagg.check_compat(header, model='tiny', kv_cache='bf16',
                        kv_block=dec.kv_block,
                        max_len=max_len)
    return dec.submit_import(
        **disagg.import_kwargs(header, arrays)).result(timeout=300)


# -- engine-level byte parity ------------------------------------------------


@pytest.mark.parametrize('block', [16, 8])
def test_greedy_parity_colocated_vs_disaggregated(tiny_params, block):
    """The headline contract: a prompt prefilled on one engine,
    exported, transferred, imported on another, decodes to EXACTLY the
    tokens a colocated engine produces — at either block length."""
    colo = _engine(tiny_params, kv_block=block)
    pre = _engine(tiny_params, role='prefill', kv_block=block)
    dec = _engine(tiny_params, role='decode', kv_block=block)
    try:
        for n, max_new, salt in ((13, 12, 0), (33, 16, 1), (1, 8, 2)):
            row = _row(n, salt)
            want = colo.submit(row, max_new).result(timeout=300)
            got = _import_tokens(dec, _handoff_bytes(pre, row, max_new))
            assert list(got) == list(want), (block, n, got, want)
        assert pre.exports == 3 and pre.imports == 0
        assert dec.imports == 3 and dec.exports == 0
        assert pre.stats()['disagg']['exports'] == 3
        assert dec.stats()['disagg']['imports'] == 3
    finally:
        for e in (colo, pre, dec):
            e.stop()


def test_paged_parity_with_prefix_share_negotiation(tiny_params):
    """Prefix references, not bytes: when the decode engine's share
    trie already holds the prompt's leading blocks, the transfer skips
    them (probe_chain -> skip_blocks -> block_start import) and greedy
    output is STILL byte-identical; the skipped payload is smaller."""
    colo = _engine(tiny_params)
    pre = _engine(tiny_params, role='prefill')
    dec = _engine(tiny_params, role='decode',
                  prefix_share=True)
    try:
        p = dec.kv_block
        shared_head = _row(2 * p, 3)
        # Warm the decode trie: a request whose prompt opens with the
        # same two full blocks, completed and drained (blocks idle in
        # the trie, refs 0).
        warm = shared_head + _row(5, 4)
        dec.submit(warm, 4).result(timeout=300)

        row = shared_head + _row(7, 5)
        skip = dec.probe_chain(row)
        assert skip == 2, skip

        want = colo.submit(row, 12).result(timeout=300)
        full = _handoff_bytes(pre, row, 12)
        skipped = _handoff_bytes(pre, row, 12, skip_blocks=skip)
        assert len(skipped) < len(full), (len(skipped), len(full))
        got = _import_tokens(dec, skipped)
        assert list(got) == list(want), (got, want)
        assert dec.share_hits >= 1  # installed as references
    finally:
        for e in (colo, pre, dec):
            e.stop()


def test_paged_parity_with_full_chain_shared(tiny_params):
    """A prompt whose length is an EXACT multiple of the block size and
    whose whole chain is already in the decode trie negotiates away
    every plane — the payload carries no block bytes at all (entry.k is
    None; the install is a pure table write) and greedy output is still
    byte-identical (review finding: this path used to crash the engine
    thread on entry.k.dtype)."""
    colo = _engine(tiny_params)
    pre = _engine(tiny_params, role='prefill')
    dec = _engine(tiny_params, role='decode',
                  prefix_share=True)
    try:
        p = dec.kv_block
        row = _row(2 * p, 8)  # exact multiple: every block is full
        dec.submit(row, 4).result(timeout=300)  # warm the whole chain
        skip = dec.probe_chain(row)
        assert skip == 2, skip
        want = colo.submit(row, 12).result(timeout=300)
        data = _handoff_bytes(pre, row, 12, skip_blocks=skip)
        header, arrays = disagg.parse(data)
        assert not header['planes'] and not arrays  # zero bytes moved
        got = dec.submit_import(
            **disagg.import_kwargs(header, arrays)).result(timeout=300)
        assert list(got) == list(want), (got, want)
    finally:
        for e in (colo, pre, dec):
            e.stop()


def test_shape_skewed_payload_rejected_before_enqueue(tiny_params):
    """A payload whose header claims wrong plane shapes (header
    corruption survives crc32, which covers plane bytes only) must be
    rejected SYNCHRONOUSLY at submit_import — an install raising on the
    engine thread would fail every in-flight request — and the engine
    keeps serving afterward."""
    pre = _engine(tiny_params, role='prefill')
    dec = _engine(tiny_params, role='decode')
    try:
        data = _handoff_bytes(pre, _row(13, 9), 8)
        header, arrays = disagg.parse(data)
        kwargs = disagg.import_kwargs(header, arrays)
        kwargs['k'] = kwargs['k'][:, :, :, :-1]  # skewed block width
        with pytest.raises(ValueError):
            dec.submit_import(**kwargs)
        missing = disagg.import_kwargs(header, arrays)
        missing['k'] = None  # planes absent without a full skip
        with pytest.raises(ValueError):
            dec.submit_import(**missing)
        # No engine-thread damage: a clean import still serves.
        good = dec.submit_import(
            **disagg.import_kwargs(header, arrays)).result(timeout=300)
        assert len(good) == 8
    finally:
        pre.stop()
        dec.stop()


def test_import_rejected_when_negotiated_blocks_evicted(tiny_params):
    """Blocks negotiated away as shared references that are gone by
    import time (evicted between prepare and import) fail the install
    with KVImportError — the serving layer's 409/fallback signal —
    instead of decoding from junk KV."""
    from skypilot_tpu.models.engine import KVImportError
    pre = _engine(tiny_params, role='prefill')
    dec = _engine(tiny_params, role='decode',
                  prefix_share=True)
    try:
        p = dec.kv_block
        row = _row(2 * p + 5, 6)
        # skip_blocks=2 but the decode trie never saw this chain.
        data = _handoff_bytes(pre, row, 8, skip_blocks=0)
        header, arrays = disagg.parse(data)
        kwargs = disagg.import_kwargs(header, arrays)
        kwargs['block_start'] = 2  # forged negotiation
        # Drop the (transferred) leading blocks like a real skip would.
        for name in ('k', 'v'):
            kwargs[name] = kwargs[name][:, 2:]
        with pytest.raises(KVImportError):
            dec.submit_import(**kwargs).result(timeout=300)
        assert dec.stats()['disagg']['import_errors'] == 1
    finally:
        pre.stop()
        dec.stop()


# -- wire format validation --------------------------------------------------


def test_corrupt_and_truncated_payloads_rejected(tiny_params):
    pre = _engine(tiny_params, role='prefill')
    try:
        data = _handoff_bytes(pre, _row(13, 7), 8)
        header, _ = disagg.parse(data)  # baseline: parses clean

        bad = bytearray(data)
        bad[len(bad) // 2] ^= 0xFF  # bit-flip in plane bytes
        with pytest.raises(disagg.DisaggFormatError):
            disagg.parse(bytes(bad))
        with pytest.raises(disagg.DisaggFormatError):
            disagg.parse(data[:-7])  # truncated plane
        with pytest.raises(disagg.DisaggFormatError):
            disagg.parse(data[:8])  # truncated header
        with pytest.raises(disagg.DisaggFormatError):
            disagg.parse(b'NOTAKVMAGIC' + data[11:])
        # Well-formed but wrong replica: compat errors, not format.
        for kw in (dict(model='other'), dict(kv_cache='int8'),
                   dict(kv_block=999), dict(max_len=10)):
            full = dict(model='tiny', kv_cache='bf16',
                        kv_block=header['block'], max_len=96)
            full.update(kw)
            with pytest.raises(disagg.DisaggCompatError):
                disagg.check_compat(header, **full)
    finally:
        pre.stop()


@pytest.mark.parametrize('theirs', ['slot', 'dense', None])
def test_a_header_of_another_layout_is_refused_by_name(theirs):
    """The wire format keeps its ``layout`` field: a prefill replica of
    a version that still had the slot layout (a rolling upgrade) is a
    compat error that names the field and both values, and so a
    colocated fallback at the LB, never an install."""
    ours = dict(model='tiny', kv_cache='bf16', kv_block=16, max_len=96)
    header = {'format': disagg.FORMAT, 'model': 'tiny', 'kv_cache': 'bf16',
              'layout': 'paged', 'block': 16, 'row': [1, 2, 3],
              'max_new': 4}
    disagg.check_compat(header, **ours)
    with pytest.raises(disagg.DisaggCompatError) as exc:
        disagg.check_compat(dict(header, layout=theirs), **ours)
    assert 'layout' in str(exc.value) and 'paged' in str(exc.value)
    assert repr(theirs) in str(exc.value)


def test_the_header_still_says_paged_and_the_import_takes_no_layout(
        tiny_params):
    pre = _engine(tiny_params, role='prefill')
    try:
        h = pre.submit_prefill(_row(21, 3), 4).result(timeout=300)
        assert not hasattr(h, 'layout') and h.n_blocks == 2
        header = disagg.build_header(h, model='tiny', kv_cache='bf16')
        assert header['layout'] == 'paged' and header['block'] == 16
        _, arrays = disagg.parse(disagg.serialize_bytes(h, header))
        assert 'layout' not in disagg.import_kwargs(header, arrays)
    finally:
        pre.stop()


def test_registry_ttl_and_staging_roundtrip(tmp_path):
    reg = disagg.HandoffRegistry(ttl_s=0.2)
    hid = reg.put('payload')
    assert reg.pop(hid) == 'payload'
    assert reg.pop(hid) is None  # one-shot
    hid2 = reg.put('stale')
    time.sleep(0.3)
    assert reg.pop(hid2) is None  # expired
    assert reg.expired >= 1

    class _Fake:
        n_blocks = 1
        k_s = None

    import numpy as np
    fake = _Fake()
    fake.k = np.arange(12, dtype=np.float32).reshape(1, 1, 1, 3, 4)
    fake.v = fake.k + 1
    header = {'format': disagg.FORMAT, 'planes': [
        {'name': n, 'block': 0, 'dtype': 'float32',
         'shape': [1, 1, 3, 4], 'nbytes': 48,
         'crc32': __import__('zlib').crc32(arr.tobytes()) & 0xFFFFFFFF}
        for n, arr in (('k', fake.k), ('v', fake.v))]}
    ref, nbytes = disagg.write_staging(str(tmp_path), fake, header)
    assert nbytes > 0
    data = disagg.read_staging(str(tmp_path), ref)
    parsed, arrays = disagg.parse(data)
    assert (arrays['k'] == fake.k).all()
    # Hostile refs cannot traverse out of the staging dir.
    with pytest.raises(disagg.DisaggError):
        disagg.read_staging(str(tmp_path), '../' + ref)
    with pytest.raises(disagg.DisaggError):
        disagg.read_staging(str(tmp_path), 'nope' + disagg.STAGING_SUFFIX)
    with pytest.raises(disagg.DisaggError):
        disagg.read_staging(None, ref)


# -- decode-pool admission backpressure --------------------------------------


def test_import_backpressure_on_kv_blocks(tiny_params):
    """An imported prompt whose block reservation does not fit QUEUES
    (visible as the queued_imports autoscaler signal) instead of
    crashing or stealing blocks, and admits once the pool frees."""
    pre = _engine(tiny_params, role='prefill')
    # 9 usable blocks (10 minus the junk sink): one 32+64 request needs
    # 6, so a second identical-footprint import must wait.
    dec = _engine(tiny_params, role='decode',
                  kv_blocks=10, prefix_share=False)
    colo = _engine(tiny_params)
    try:
        row_a, row_b = _row(32, 8), _row(32, 9)
        want_a = colo.submit(row_a, 64).result(timeout=300)
        want_b = colo.submit(row_b, 64).result(timeout=300)
        seen_a = threading.Event()
        data_a = _handoff_bytes(pre, row_a, 64)
        data_b = _handoff_bytes(pre, row_b, 64)
        header, arrays = disagg.parse(data_a)
        kw = disagg.import_kwargs(header, arrays)
        kw['on_tokens'] = lambda toks: seen_a.set()
        fut_a = dec.submit_import(**kw)
        assert seen_a.wait(120)  # A admitted and decoding
        header, arrays = disagg.parse(data_b)
        fut_b = dec.submit_import(**disagg.import_kwargs(header, arrays))
        deadline = time.time() + 60
        queued = 0
        while time.time() < deadline:
            queued = dec.stats()['disagg']['queued_imports']
            if queued and not fut_a.done():
                break
            if fut_a.done():
                break
            time.sleep(0.01)
        assert queued >= 1, 'import B never queued behind A'
        assert not fut_b.done()
        assert list(fut_a.result(timeout=300)) == list(want_a)
        assert list(fut_b.result(timeout=300)) == list(want_b)
    finally:
        for e in (pre, dec, colo):
            e.stop()


# -- HTTP / LB integration ---------------------------------------------------


def _start_http(server, port_base):
    from aiohttp import web

    from skypilot_tpu.utils import common_utils
    port = common_utils.find_free_port(port_base)
    started = threading.Event()

    def run():
        loop = asyncio.new_event_loop()
        asyncio.set_event_loop(loop)
        runner = web.AppRunner(server.make_app())
        loop.run_until_complete(runner.setup())
        site = web.TCPSite(runner, '127.0.0.1', port)
        loop.run_until_complete(site.start())
        started.set()
        loop.run_forever()

    threading.Thread(target=run, daemon=True).start()
    assert started.wait(30)
    return f'127.0.0.1:{port}'


@pytest.fixture(scope='module')
def disagg_fleet():
    """A prefill + decode + colocated replica trio behind a role-aware
    LB (module-scoped: three tiny engines cost seconds, shared across
    the HTTP tests; each test uses distinct prompts)."""
    from skypilot_tpu.serve import llm_server as llm_mod
    from skypilot_tpu.serve.load_balancer import LoadBalancer
    from skypilot_tpu.utils import common_utils
    os.environ.pop(disagg.STAGING_ENV, None)
    servers = {
        role: llm_mod.LlmServer('tiny', max_len=96,
                                role=role)
        for role in ('prefill', 'decode', 'colocated')}
    eps = {role: _start_http(s, 23900 + 20 * i)
           for i, (role, s) in enumerate(servers.items())}
    lb = LoadBalancer(common_utils.find_free_port(24100))
    lb.set_replicas(list(eps.values()),
                    roles={ep: role for role, ep in eps.items()})
    lb.start_in_thread()
    try:
        yield servers, eps, lb
    finally:
        lb.stop()
        for s in servers.values():
            if s.engine is not None:
                s.engine.stop()


def test_http_disagg_parity_and_metrics(disagg_fleet):
    servers, eps, lb = disagg_fleet
    payload = {'tokens': [_row(21, 10)], 'max_new_tokens': 10}
    direct = requests_lib.post(f'http://{eps["colocated"]}/generate',
                               json=payload, timeout=300)
    assert direct.status_code == 200
    via_lb = requests_lib.post(f'http://127.0.0.1:{lb.port}/generate',
                               json=payload, timeout=300)
    assert via_lb.status_code == 200, via_lb.text
    assert via_lb.json() == direct.json()
    assert via_lb.headers.get('X-SkyTPU-Disagg') == 'remote'
    assert via_lb.headers.get('X-Served-By') == eps['decode']
    assert lb.disagg_stats['handoffs'] == 1
    # Handoff accounting surfaces on /health and the replica scrape.
    h_pre = requests_lib.get(f'http://{eps["prefill"]}/health',
                             timeout=30).json()
    assert h_pre['role'] == 'prefill'
    assert h_pre['disagg']['exports'] == 1
    assert h_pre['disagg']['export_bytes'] > 0
    h_dec = requests_lib.get(f'http://{eps["decode"]}/health',
                             timeout=30).json()
    assert h_dec['role'] == 'decode'
    assert h_dec['disagg']['imports'] == 1
    assert h_dec['disagg']['import_bytes'] > 0
    scrape = requests_lib.get(f'http://{eps["decode"]}/metrics',
                              timeout=30).text
    assert 'skytpu_disagg_handoff_bytes{direction="import"}' in scrape
    for line in scrape.splitlines():
        if line.startswith('skytpu_disagg_handoff_bytes'
                           '{direction="import"}'):
            assert float(line.rsplit(' ', 1)[1]) > 0, line


def test_http_export_respects_qos_admission(tiny_params, monkeypatch):
    """QoS admission gates /v1/kv/export — a disaggregated fleet must
    not be a per-tenant quota bypass (review finding): with the tenant
    req/s bucket exhausted the export sheds 429 + Retry-After and the
    engine does no prefill work; the granted export before it still
    serves (ticket released, nothing leaks)."""
    from skypilot_tpu.serve import llm_server as llm_mod
    monkeypatch.setenv('SKYTPU_QOS', '1')
    # rate ~0, burst floor 1.0: exactly one export is admitted.
    monkeypatch.setenv('SKYTPU_QOS_TENANT_RPS', '0.001')
    server = llm_mod.LlmServer('tiny', max_len=96,
                               role='prefill')
    ep = _start_http(server, 24300)
    try:
        first = requests_lib.post(
            f'http://{ep}/v1/kv/export',
            json={'tokens': [_row(9, 12)], 'max_new_tokens': 6},
            timeout=300)
        assert first.status_code == 200, first.text
        assert server.disagg_stats['exports'] == 1
        second = requests_lib.post(
            f'http://{ep}/v1/kv/export',
            json={'tokens': [_row(9, 13)], 'max_new_tokens': 6},
            timeout=300)
        assert second.status_code == 429, (second.status_code,
                                           second.text)
        assert second.headers.get('Retry-After')
        assert server.disagg_stats['exports'] == 1  # no work done
        assert server.qos.stats()['shed_total'] == 1
    finally:
        if server.engine is not None:
            server.engine.stop()


def test_http_corrupt_handoff_rejected_and_fallback(disagg_fleet):
    """A corrupt payload POSTed to /v1/kv/import is rejected (400,
    nothing installed), and when a handoff leg fails the LB re-serves
    the request whole on the main pool with the fallback marker."""
    servers, eps, lb = disagg_fleet
    pre_ep, dec_ep = eps['prefill'], eps['decode']
    payload = {'tokens': [_row(17, 11)], 'max_new_tokens': 8}
    # Manual handoff with corruption injected between fetch and import.
    exp = requests_lib.post(f'http://{pre_ep}/v1/kv/export',
                            json=payload, timeout=300).json()
    data = requests_lib.get(
        f'http://{pre_ep}/v1/kv/fetch',
        params={'handoff': exp['handoff']}, timeout=300).content
    bad = bytearray(data)
    bad[-5] ^= 0xFF
    rejects0 = servers['decode'].disagg_stats['import_rejects']
    r = requests_lib.post(
        f'http://{dec_ep}/v1/kv/import', data=bytes(bad),
        headers={'Content-Type': 'application/octet-stream'},
        timeout=300)
    assert r.status_code == 400, r.text
    assert 'crc32' in r.json()['error']
    assert servers['decode'].disagg_stats['import_rejects'] \
        == rejects0 + 1
    # Failing prefill pool: point the LB's prefill role at a dead
    # endpoint — export cannot even connect, and the LB must fall back
    # to colocated serving; the request still succeeds byte-identically.
    try:
        fallbacks0 = lb.disagg_stats['fallbacks']
        lb.set_replicas(['127.0.0.1:9', eps['decode'],
                         eps['colocated']],
                        roles={'127.0.0.1:9': 'prefill',
                               eps['decode']: 'decode',
                               eps['colocated']: 'colocated'})
        via_lb = requests_lib.post(
            f'http://127.0.0.1:{lb.port}/generate',
            json=payload, timeout=300)
        assert via_lb.status_code == 200, via_lb.text
        direct = requests_lib.post(f'http://{eps["colocated"]}/generate',
                                   json=payload, timeout=300)
        assert via_lb.json() == direct.json()
        assert lb.disagg_stats['fallbacks'] == fallbacks0 + 1
        served_by = via_lb.headers.get('X-Served-By')
        assert served_by in (eps['decode'], eps['colocated'])
        fb = sum(servers[r].disagg_stats['fallbacks_served']
                 for r in ('decode', 'colocated'))
        assert fb >= 1  # the replica counted the fallback marker
    finally:
        lb.set_replicas(list(eps.values()),
                        roles={ep: role for role, ep in eps.items()})


def _midstream_kill_attempt(salt: int, port_base: int):
    """One attempt of the decode-dies-mid-stream scenario; returns
    (got_tokens, want_tokens, resumed, colocated_fallbacks). ``resumed``
    is False when the tiny-model decode outran the kill (the whole
    stream was already emitted) — the caller retries."""
    from skypilot_tpu.serve import llm_server as llm_mod
    from skypilot_tpu.serve.load_balancer import LoadBalancer
    from skypilot_tpu.utils import common_utils
    os.environ.pop(disagg.STAGING_ENV, None)
    servers = {
        role: llm_mod.LlmServer('tiny', max_len=160,
                                role=role)
        for role in ('prefill', 'decode', 'colocated')}
    # Per-token emission lines: the more lines, the wider the window
    # for the kill to land mid-stream.
    for s in servers.values():
        s.engine.chunk_steps = 1
    eps = {role: _start_http(s, port_base + 20 * i)
           for i, (role, s) in enumerate(servers.items())}
    lb = LoadBalancer(common_utils.find_free_port(port_base + 70))
    lb.set_replicas(list(eps.values()),
                    roles={ep: role for role, ep in eps.items()})
    lb.start_in_thread()
    try:
        row = _row(19, salt)
        payload = {'tokens': [row], 'max_new_tokens': 128,
                   'stream': True}
        want = requests_lib.post(
            f'http://{eps["colocated"]}/generate',
            json={**payload, 'stream': False}, timeout=300
        ).json()['tokens'][0]

        # The client's own trace header: after a mid-stream kill BOTH
        # legs (dead decode + surviving resume) must stitch into THIS
        # one trace id — the resume retry re-sends the original header.
        from skypilot_tpu.observability import trace as trace_lib
        trace_lib.reset()
        header = trace_lib.make_header()
        trace_id = header.split('-')[1]
        got = []
        killed = False
        with requests_lib.post(f'http://127.0.0.1:{lb.port}/generate',
                               json=payload, stream=True,
                               headers={trace_lib.TRACE_HEADER: header},
                               timeout=300) as r:
            assert r.status_code == 200
            for line in r.iter_lines():
                if not line:
                    continue
                obj = json.loads(line)
                assert 'error' not in obj, obj
                if obj.get('done'):
                    break
                got.extend(obj.get('tokens') or [])
                if not killed and got:
                    # Kill the decode engine mid-stream: its in-flight
                    # future fails, the replica writes an in-band error
                    # line, and the LB must resume elsewhere.
                    servers['decode'].engine.stop()
                    killed = True
        assert killed, 'no tokens before stream end'
        return (got, list(want), lb.disagg_stats['resumed_streams'],
                servers['colocated'].disagg_stats['fallbacks_served'],
                trace_id)
    finally:
        lb.stop()
        for s in servers.values():
            if s.engine is not None:
                s.engine.stop()


def test_http_lb_reroutes_when_decode_dies_midstream():
    """The decode replica's engine dies mid-stream: the LB resumes the
    request on a surviving replica, skipping tokens already delivered —
    the client sees ONE complete, correct stream, and both legs stitch
    into ONE trace (the resume retry re-sends the original
    X-SkyTPU-Trace header and tags the survivor leg resume=true)
    retained under the 'resumed' verdict. Retried because the tiny
    model can finish all 128 tokens before the kill lands (the race is
    the test's point, not a flake)."""
    from skypilot_tpu.observability import trace as trace_lib
    for attempt in range(3):
        got, want, resumed, fallbacks, trace_id = \
            _midstream_kill_attempt(
                salt=12 + attempt, port_base=24200 + 200 * attempt)
        assert got == want, (got, want)
        if resumed:
            assert fallbacks == 1
            # All servers + the LB share this process's tracer: every
            # fragment of the journey must carry the CLIENT's trace id
            # (one trace, not orphans) with the resume evidence intact.
            traces = trace_lib.collect(trace_id=trace_id, limit=10,
                                       include_exported=False)
            assert len(traces) == 1, [t['trace_id'] for t in traces]
            tr = traces[0]
            names = {s['name'] for s in tr['spans']}
            assert 'lb.request' in names, sorted(names)
            # The survivor leg re-joined the SAME trace and is tagged.
            resumed_legs = [
                s for s in tr['spans']
                if s['name'] == 'serve.generate'
                and (s.get('attrs') or {}).get('resume')]
            assert resumed_legs, [
                (s['name'], s.get('attrs')) for s in tr['spans']]
            assert tr['attrs'].get('resume') is True  # LB root attr
            # Retention kept the journey as 'resumed'.
            assert tr.get('retained') == 'resumed', tr.get('retained')
            return
    raise AssertionError(
        'decode finished before the kill in all 3 attempts — '
        'could not exercise the mid-stream re-route')


def test_http_staging_fast_path(tiny_params, tmp_path, monkeypatch):
    """Same-host fast path: with SKYTPU_DISAGG_STAGING set the payload
    moves as a staging ref (zero KV bytes over HTTP) and greedy output
    still matches colocated."""
    from skypilot_tpu.serve import llm_server as llm_mod
    from skypilot_tpu.serve.load_balancer import LoadBalancer
    from skypilot_tpu.utils import common_utils
    monkeypatch.setenv(disagg.STAGING_ENV, str(tmp_path))
    servers = {
        role: llm_mod.LlmServer('tiny', max_len=96,
                                role=role)
        for role in ('prefill', 'decode')}
    eps = {role: _start_http(s, 24500 + 20 * i)
           for i, (role, s) in enumerate(servers.items())}
    lb = LoadBalancer(common_utils.find_free_port(24700))
    lb.set_replicas(list(eps.values()),
                    roles={ep: role for role, ep in eps.items()})
    lb.start_in_thread()
    try:
        payload = {'tokens': [_row(26, 13)], 'max_new_tokens': 9}
        direct = requests_lib.post(f'http://{eps["decode"]}/generate',
                                   json=payload, timeout=300)
        via_lb = requests_lib.post(f'http://127.0.0.1:{lb.port}/generate',
                                   json=payload, timeout=300)
        assert via_lb.status_code == 200, via_lb.text
        assert via_lb.json() == direct.json()
        assert via_lb.headers.get('X-SkyTPU-Disagg') == 'staged'
        h = requests_lib.get(f'http://{eps["prefill"]}/health',
                             timeout=30).json()
        assert h['disagg']['staging'] is True
        assert h['disagg']['exports'] == 1
    finally:
        lb.stop()
        for s in servers.values():
            if s.engine is not None:
                s.engine.stop()


# -- per-replica request-time attribution (LB satellite fix) -----------------


def test_lb_drain_request_times_per_replica():
    from skypilot_tpu.serve.load_balancer import LoadBalancer
    lb = LoadBalancer(port=0)
    lb._note_request('a:1')
    lb._note_request('a:1')
    lb._note_request('b:2')
    by_rep = lb.drain_request_times_by_replica()
    assert len(by_rep['a:1']) == 2
    assert len(by_rep['b:2']) == 1
    flat = lb.drain_request_times()
    assert len(flat) == 3 and flat == sorted(flat)
    # Window pruning drops stale buckets entirely.
    with lb._times_lock:
        lb._times['a:1'] = [time.time() - 999]
    by_rep = lb.drain_request_times_by_replica(window_seconds=120.0)
    assert 'a:1' not in by_rep


# -- DualPoolAutoscaler ------------------------------------------------------


def _replica(rid, role, status='READY', health=None):
    return {'replica_id': rid, 'role': role, 'status': status,
            'endpoint': f'r{rid}:80', 'weight': 1.0,
            'health': json.dumps(health) if health else None}


def _policy(**kw):
    from skypilot_tpu.serve.service_spec import ReplicaPolicy
    cfg = {'disagg': {'prefill': {'min_replicas': 1, 'max_replicas': 3},
                      'decode': {'min_replicas': 1, 'max_replicas': 4}},
           'target_queue_per_replica': 2,
           'target_decode_tok_s_per_replica': 100}
    cfg.update(kw)
    return ReplicaPolicy.from_config(cfg)


def test_dual_pool_autoscaler_scales_each_pool_on_its_signal():
    from skypilot_tpu.serve.autoscalers import (DualPoolAutoscaler,
                                                make_autoscaler)
    policy = _policy()
    assert policy.disaggregated
    scaler = make_autoscaler(policy)
    assert isinstance(scaler, DualPoolAutoscaler)

    def snap(queue_depth, tokens, free, usable, t):
        reps = [
            _replica(1, 'prefill', health={
                'queue': {'depth_total': queue_depth},
                'engine': {'tokens_emitted': 0,
                           'prefill_bubble_ms': 0}}),
            _replica(2, 'decode', health={
                'queue': {'depth_total': 0},
                'engine': {'tokens_emitted': tokens,
                           'kv_blocks': {'free': free,
                                         'usable': usable}}}),
        ]
        return scaler.evaluate(2, 0, [], now=t, replicas=reps)

    # Tick 1 primes the rate trackers; no signal -> hold at minimums.
    d = snap(0, 0, 9, 10, t=1000.0)
    assert (d.num_prefill, d.num_decode) == (1, 1)
    # Prefill queue blows past target (6 queued / 2 per replica -> 3)
    # while decode stays cold: only the prefill pool grows (after the
    # 2-tick upscale hysteresis).
    d = snap(6, 10, 9, 10, t=1010.0)
    d = snap(6, 20, 9, 10, t=1020.0)
    assert d.num_prefill == 3, d
    assert d.num_decode == 1, d
    assert 'prefill queue' in d.reason
    # Decode pool: tok/s signal (3000 tokens / 10 s = 300 tok/s ->
    # 3 replicas at 100 tok/s each) scales decode, prefill falls back
    # once its queue drains (5-tick downscale hysteresis).
    t = 1020.0
    for _ in range(2):
        t += 10.0
        d = snap(0, (t - 1020.0) * 300 + 20, 9, 10, t=t)
    assert d.num_decode == 3, d
    assert 'decode' in d.reason


def test_dual_pool_occupancy_grows_decode():
    """KV-block occupancy past the high-water mark grows the decode
    pool even at zero throughput: imported prompts queue for BLOCKS,
    so the pool is memory-bound, not compute-bound."""
    from skypilot_tpu.serve.autoscalers import make_autoscaler
    scaler = make_autoscaler(_policy())

    def reps(free):
        return [
            _replica(1, 'prefill', health={
                'queue': {'depth_total': 0},
                'engine': {'tokens_emitted': 0,
                           'prefill_bubble_ms': 0}}),
            _replica(2, 'decode', health={'engine': {
                'tokens_emitted': 0,
                'kv_blocks': {'free': free, 'usable': 10}}}),
            _replica(3, 'decode', health={'engine': {
                'tokens_emitted': 0,
                'kv_blocks': {'free': free, 'usable': 10}}}),
        ]

    d = scaler.evaluate(3, 0, [], now=1000.0, replicas=reps(9))  # prime
    assert 'occupancy' not in d.reason
    d = scaler.evaluate(3, 0, [], now=1010.0, replicas=reps(0))
    d = scaler.evaluate(3, 0, [], now=1020.0, replicas=reps(0))
    assert d.num_decode == 3, d  # two alive + one more
    assert 'occupancy' in d.reason


def test_dual_pool_spec_roundtrip_and_validation():
    from skypilot_tpu.serve.service_spec import ServiceSpec
    spec = ServiceSpec.from_yaml_config({
        'replica_policy': {
            'disagg': {'prefill': 1, 'decode': {'min_replicas': 2,
                                                'max_replicas': 5}},
            'target_decode_tok_s_per_replica': 500,
        },
        'port': 9000,
    })
    assert spec.replica_policy.disaggregated
    assert spec.replica_policy.prefill_pool.min_replicas == 1
    assert spec.replica_policy.decode_pool.max_replicas == 5
    cfg = spec.to_yaml_config()
    spec2 = ServiceSpec.from_yaml_config(cfg)
    assert spec2.replica_policy.decode_pool.max_replicas == 5
    assert spec2.replica_policy.target_decode_tok_s_per_replica == 500
    with pytest.raises(ValueError, match='BOTH'):
        ServiceSpec.from_yaml_config({
            'replica_policy': {'disagg': {'prefill': 1}}})
