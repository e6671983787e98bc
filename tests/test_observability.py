"""Log shipping, usage telemetry, and request-tracing tests (SURVEY §5
observability)."""
import json
import os
import pathlib
import time

import pytest

from skypilot_tpu import logs as logs_lib
from skypilot_tpu import usage
from skypilot_tpu.observability import trace


def test_log_agents_render_fluentbit_configs(monkeypatch):
    gcp = logs_lib.GcpLogAgent(project_id='p1')
    cfg = gcp.fluentbit_config('c1')
    assert '[INPUT]' in cfg and 'tail' in cfg
    assert 'stackdriver' in cfg and 'cluster=c1' in cfg
    cmd = gcp.install_command('c1')
    assert 'fluent-bit' in cmd and 'nohup' in cmd

    aws = logs_lib.AwsLogAgent(region='eu-west-1', log_group='g')
    cfg = aws.fluentbit_config('c2')
    assert 'cloudwatch_logs' in cfg and 'eu-west-1' in cfg
    assert 'log_stream_prefix c2-' in cfg


def test_log_store_registry(monkeypatch):
    assert logs_lib.agent_from_config() is None  # off by default
    from skypilot_tpu import config as config_lib
    monkeypatch.setattr(config_lib, 'get_nested',
                        lambda path, default=None: 'gcp'
                        if path == ('logs', 'store') else default)
    agent = logs_lib.agent_from_config()
    assert isinstance(agent, logs_lib.GcpLogAgent)


def test_usage_records_spool(tmp_state_dir, monkeypatch):
    monkeypatch.delenv('SKYTPU_DISABLE_USAGE_COLLECTION', raising=False)
    monkeypatch.delenv('SKYTPU_USAGE_ENDPOINT', raising=False)
    usage.record('test-event', foo=1)
    spool = os.path.join(str(tmp_state_dir), 'usage')
    files = os.listdir(spool)
    assert len(files) == 1
    with open(os.path.join(spool, files[0]), encoding='utf-8') as f:
        msg = json.loads(f.read().splitlines()[-1])
    assert msg['event'] == 'test-event' and msg['foo'] == 1
    # anonymized: a hash, not the raw username
    import getpass
    assert getpass.getuser() not in json.dumps(msg)


def test_usage_opt_out(tmp_state_dir, monkeypatch):
    monkeypatch.setenv('SKYTPU_DISABLE_USAGE_COLLECTION', '1')
    usage.record('nope')
    assert not os.path.exists(os.path.join(str(tmp_state_dir), 'usage'))


def test_usage_spool_rotation_file_count(tmp_state_dir, monkeypatch):
    """Satellite: the spool is bounded — oldest files rotate out, the
    live (newest) file survives."""
    monkeypatch.delenv('SKYTPU_DISABLE_USAGE_COLLECTION', raising=False)
    monkeypatch.setenv('SKYTPU_USAGE_SPOOL_MAX_FILES', '3')
    spool = os.path.join(str(tmp_state_dir), 'usage')
    os.makedirs(spool, exist_ok=True)
    for i in range(6):
        path = os.path.join(spool, f'2020010{i}.jsonl')
        with open(path, 'w', encoding='utf-8') as f:
            f.write('{"old": true}\n')
        os.utime(path, (1_000_000 + i, 1_000_000 + i))
    usage.record('rotated')
    files = sorted(os.listdir(spool))
    assert len(files) == 3, files
    assert time.strftime('%Y%m%d') + '.jsonl' in files  # live file kept
    assert '20200100.jsonl' not in files  # oldest evicted first


def test_usage_spool_rotation_byte_bound(tmp_state_dir, monkeypatch):
    monkeypatch.delenv('SKYTPU_DISABLE_USAGE_COLLECTION', raising=False)
    # ~1 KB bound: the padded old file must rotate out; the live file
    # survives even though it alone may approach the bound.
    monkeypatch.setenv('SKYTPU_USAGE_SPOOL_MAX_MB', '0.001')
    spool = os.path.join(str(tmp_state_dir), 'usage')
    os.makedirs(spool, exist_ok=True)
    big = os.path.join(spool, '20200101.jsonl')
    with open(big, 'w', encoding='utf-8') as f:
        f.write('x' * 4096)
    os.utime(big, (1_000_000, 1_000_000))
    usage.record('byte-bound')
    files = os.listdir(spool)
    assert '20200101.jsonl' not in files
    assert files == [time.strftime('%Y%m%d') + '.jsonl']


def test_usage_entrypoint_times_and_records_errors(tmp_state_dir,
                                                   monkeypatch):
    monkeypatch.delenv('SKYTPU_DISABLE_USAGE_COLLECTION', raising=False)

    @usage.entrypoint('boom')
    def boom():
        raise ValueError('x')

    with pytest.raises(ValueError):
        boom()
    spool = os.path.join(str(tmp_state_dir), 'usage')
    content = open(os.path.join(spool, os.listdir(spool)[0]),
                   encoding='utf-8').read()
    msg = json.loads(content.splitlines()[-1])
    assert msg['event'] == 'boom' and msg['ok'] is False
    assert msg['error'] == 'ValueError'


# -- request tracing (observability/trace.py) --------------------------------


@pytest.fixture()
def traced(monkeypatch):
    monkeypatch.setenv('SKYTPU_TRACE', '1')
    monkeypatch.delenv('SKYTPU_TRACE_SAMPLE', raising=False)
    monkeypatch.delenv('SKYTPU_TRACE_EXPORT', raising=False)
    # Baseline keeps (2/min by default) would add nondeterministic
    # keep-* files / retained records to the legacy assertions below;
    # the retention tests opt back in explicitly.
    monkeypatch.setenv('SKYTPU_TRACE_TAIL_BASELINE_PER_MIN', '0')
    trace.reset()
    yield
    trace.reset()


def test_trace_header_roundtrip_and_rejection(traced, monkeypatch):
    h = trace.make_header()
    tid, sid, sampled = trace.parse_header(h)
    assert sampled and len(tid) == 32 and len(sid) == 16
    assert trace.parse_header(None) is None
    assert trace.parse_header('') is None
    assert trace.parse_header('nonsense') is None
    assert trace.parse_header('00-zz-yy-01') is None
    # Unsampled flag parses; with tail retention OFF it suppresses
    # local tracing entirely...
    _, _, sampled = trace.parse_header(trace.make_header(sampled=False))
    assert sampled is False
    monkeypatch.setenv('SKYTPU_TRACE_TAIL', '0')
    assert not trace.start_trace('x', parent_header=trace.make_header(
        sampled=False))
    # ...while with tail retention ON (the default) the request is
    # still traced — into the pending/verdict path, not the ring — and
    # the outbound header preserves the unsampled flag.
    monkeypatch.setenv('SKYTPU_TRACE_TAIL', '1')
    monkeypatch.setenv('SKYTPU_TRACE_TAIL_BASELINE_PER_MIN', '0')
    tctx = trace.start_trace('x', parent_header=trace.make_header(
        sampled=False))
    assert tctx
    with tctx:
        assert trace.header_value().endswith('-00')
    assert trace.collect(include_exported=False) == []  # not in ring


def test_trace_span_nesting_and_attrs(traced):
    with trace.start_trace('root', kind='test') as root:
        assert trace.current() is root
        outbound = trace.header_value()
        with trace.span('child') as child:
            trace.set_attr(phase='inner')
            assert trace.current() is child
        trace.add_span('retro', child.start, child.end, parent=child,
                       tokens=7)
        assert trace.current() is root
    assert trace.current() is None
    recs = trace.collect(include_exported=False)
    assert len(recs) == 1
    tr = recs[0]
    by_name = {s['name']: s for s in tr['spans']}
    assert set(by_name) == {'root', 'child', 'retro'}
    assert by_name['child']['parent_id'] == by_name['root']['span_id']
    assert by_name['retro']['parent_id'] == by_name['child']['span_id']
    assert by_name['child']['attrs']['phase'] == 'inner'
    assert by_name['retro']['attrs']['tokens'] == 7
    assert tr['name'] == 'root' and tr['attrs']['kind'] == 'test'
    # The outbound header carries this trace's id.
    assert outbound.split('-')[1] == tr['trace_id']


def test_trace_join_via_header_and_request_correlation(traced):
    """A client-sent X-SkyTPU-Trace header correlates the server-side
    trace: same trace id, parent = the client's span id."""
    h = trace.make_header()
    tid, client_span, _ = trace.parse_header(h)
    with trace.start_trace('serve.generate',
                           headers={trace.TRACE_HEADER: h}) as root:
        assert root.trace_id == tid
        assert root.parent_id == client_span
    assert trace.collect(trace_id=tid,
                         include_exported=False)[0]['trace_id'] == tid


def test_trace_disabled_and_sample_zero_are_noops(traced, monkeypatch):
    monkeypatch.setenv('SKYTPU_TRACE', '0')
    assert not trace.start_trace('x')
    with trace.start_trace('x') as s:
        assert s is None
    assert trace.span('y') is not None  # no-op CM, still usable
    monkeypatch.setenv('SKYTPU_TRACE', '1')
    monkeypatch.setenv('SKYTPU_TRACE_SAMPLE', '0')
    # Head sampling off AND tail retention off: a true no-op.
    monkeypatch.setenv('SKYTPU_TRACE_TAIL', '0')
    assert not trace.start_trace('x')
    assert trace.collect(include_exported=False) == []
    # With tail retention (the default) a sample-0 root is still
    # traced — tail-pending, never in the ring.
    monkeypatch.setenv('SKYTPU_TRACE_TAIL', '1')
    monkeypatch.setenv('SKYTPU_TRACE_TAIL_BASELINE_PER_MIN', '0')
    with trace.start_trace('x') as s:
        assert s is not None and s.sampled is False
    assert trace.collect(include_exported=False) == []
    assert trace.tail_stats()['pending'] == 1
    # span() outside any trace: no-op, nothing recorded.
    with trace.span('orphan'):
        pass
    assert trace.collect(include_exported=False) == []


def test_trace_ring_is_bounded(traced, monkeypatch):
    monkeypatch.setenv('SKYTPU_TRACE_RING', '4')
    for i in range(10):
        with trace.start_trace(f't{i}'):
            pass
    recs = trace.collect(include_exported=False, limit=100)
    assert len(recs) == 4
    assert {r['name'] for r in recs} == {'t6', 't7', 't8', 't9'}


def test_trace_export_merges_across_processes(traced, monkeypatch,
                                              tmp_path):
    """The API-server flow: the middleware's record lives in this
    process's ring; the request runner's record (same trace id, rooted
    under the middleware span via the propagated header) arrives as an
    export file — collect() must stitch them into ONE trace, deduping
    any span present in both sources."""
    monkeypatch.setenv('SKYTPU_TRACE_EXPORT_DIR', str(tmp_path))
    with trace.start_trace('api.launch', request_id='r-1') as root:
        header = trace.header_value()
    assert os.listdir(tmp_path) == []  # middleware record: ring only
    # "Runner": joins via the header, exports its record on completion
    # (its record also lands in this test process's ring — the span
    # dedup must not double them).
    monkeypatch.setenv('SKYTPU_TRACE_EXPORT', '1')
    with trace.start_trace('api.run.launch', parent_header=header):
        with trace.span('launch.provision'):
            pass
    assert len(os.listdir(tmp_path)) == 1  # exported
    merged = trace.collect(trace_id=root.trace_id)
    assert len(merged) == 1
    names = [s['name'] for s in merged[0]['spans']]
    assert len(names) == len(set(names)) == 3  # deduped, both sources
    assert {'api.launch', 'api.run.launch', 'launch.provision'} \
        == set(names)
    assert merged[0]['name'] == 'api.launch'  # the true (parentless) root
    runner_root = [s for s in merged[0]['spans']
                   if s['name'] == 'api.run.launch'][0]
    assert runner_root['parent_id'] == root.span_id
    # The export file ALONE must also reattach once the runner process
    # is gone from memory (fresh server ring after a restart).
    trace.reset()
    from_file = trace.collect(trace_id=root.trace_id)
    assert len(from_file) == 1
    assert {s['name'] for s in from_file[0]['spans']} == \
        {'api.run.launch', 'launch.provision'}


def test_trace_export_rotation(traced, monkeypatch, tmp_path):
    monkeypatch.setenv('SKYTPU_TRACE_EXPORT_DIR', str(tmp_path))
    monkeypatch.setenv('SKYTPU_TRACE_EXPORT', '1')
    monkeypatch.setenv('SKYTPU_TRACE_EXPORT_KEEP', '5')
    for i in range(12):
        with trace.start_trace(f'e{i}'):
            pass
    assert len(list(tmp_path.glob('*.json'))) == 5


def test_debug_payload_filters(traced):
    with trace.start_trace('serve.generate', qos_class='interactive',
                           tenant='alice'):
        pass
    with trace.start_trace('serve.generate', qos_class='batch',
                           tenant='bob'):
        pass
    p = trace.debug_payload({'qos_class': 'interactive'})
    assert p['count'] == 1
    assert p['traces'][0]['attrs']['tenant'] == 'alice'
    p = trace.debug_payload({'tenant': 'bob'})
    assert p['count'] == 1
    p = trace.debug_payload({'limit': '1', 'slowest': '1'})
    assert p['count'] == 1


class _ChunkyEngine:
    """Stub engine emitting two chunks through on_tokens. With
    ``timelines``, its futures are the engine's own kind and carry the
    stamps a real engine would have taken."""
    slots = 4

    def __init__(self, timelines=False):
        self.timelines = timelines

    def submit(self, row, max_new, temperature=0.0, top_k=0,
               top_p=1.0, eos=None, on_tokens=None):
        import concurrent.futures as cf
        import threading
        fut = cf.Future()
        if self.timelines:
            from skypilot_tpu.models import engine as engine_lib
            line = engine_lib.RequestTimeline(time.perf_counter())
            fut = engine_lib.EngineFuture(line)

        def run():
            half = max(max_new // 2, 1)
            if self.timelines:
                time.sleep(0.02)
                line.admitted_at(time.perf_counter(), 'shared')
                line.saved_tokens = 2
                time.sleep(0.005)
                line.prefill = time.perf_counter()
                time.sleep(0.02)
                line.first = time.perf_counter()
            if on_tokens is not None:
                on_tokens([1] * half)
                time.sleep(0.01)
                on_tokens([1] * (max_new - half))
            if self.timelines:
                line.last = time.perf_counter()
            fut.set_result([1] * max_new)

        threading.Thread(target=run, daemon=True).start()
        return fut

    def stats(self):
        return {'slots': self.slots}

    def stop(self):
        pass


def _serve_stub(engine, base_port):
    """A QoS-on replica over ``engine`` on a port of its own; its URL."""
    import asyncio
    import threading

    from aiohttp import web

    from skypilot_tpu.serve import llm_server as llm_mod
    from skypilot_tpu.utils import common_utils

    server = llm_mod.LlmServer(
        'tiny', max_len=64, engine='off', qos='on',
        qos_opts=dict(max_inflight=2, max_queue=8,
                      ttl_s={'interactive': 30.0, 'standard': 30.0,
                             'batch': 30.0},
                      tenant_rps=0, tenant_tps=0))
    server.engine = engine
    port = common_utils.find_free_port(base_port)
    started = threading.Event()

    def run():
        loop = asyncio.new_event_loop()
        asyncio.set_event_loop(loop)
        runner = web.AppRunner(server.make_app())
        loop.run_until_complete(runner.setup())
        loop.run_until_complete(
            web.TCPSite(runner, '127.0.0.1', port).start())
        started.set()
        loop.run_forever()

    threading.Thread(target=run, daemon=True).start()
    assert started.wait(15)
    return f'http://127.0.0.1:{port}'


def test_llm_server_traces_serving_phases(traced, monkeypatch):
    """HTTP-level: a QoS-on replica (stub engine that emits chunk
    callbacks) produces a serve.generate trace whose phases cover
    queue-wait -> prefill -> decode, and whose histograms fill — no
    real jax decode needed."""
    import requests as requests_lib

    url = _serve_stub(_ChunkyEngine(), 23600)

    header = trace.make_header()
    r = requests_lib.post(
        f'{url}/generate',
        json={'tokens': [[1, 2, 3]], 'max_new_tokens': 4,
              'priority': 'interactive'},
        headers={trace.TRACE_HEADER: header,
                 'X-SkyTPU-Tenant': 'tracer'}, timeout=30)
    assert r.status_code == 200 and r.json()['tokens'] == [[1, 1, 1, 1]]

    tid = trace.parse_header(header)[0]
    body = requests_lib.get(f'{url}/debug/traces',
                            params={'trace_id': tid}, timeout=10).json()
    assert body['count'] == 1, body
    tr = body['traces'][0]
    assert tr['trace_id'] == tid  # joined the client's trace
    assert tr['attrs']['qos_class'] == 'interactive'
    assert tr['attrs']['tenant'] == 'tracer'
    names = [s['name'] for s in tr['spans']]
    for needed in ('serve.generate', 'qos.queue_wait', 'serve.prefill',
                   'serve.decode', 'serve.decode.chunk'):
        assert needed in names, names
    for s in tr['spans']:  # every span closed, no negative durations
        assert s['end'] is not None and s['end'] >= s['start']
    # The replica's native scrape carries the per-class histograms.
    text = requests_lib.get(f'{url}/metrics', timeout=10).text
    assert 'skytpu_serve_ttft_seconds_bucket{' in text
    assert 'qos_class="interactive"' in text
    assert 'skytpu_serve_queue_wait_seconds_count' in text
    assert 'skytpu_replica_slots 4.0' in text


def test_serve_prefill_span_holds_the_engines_three_waits(traced):
    """Under ``serve.prefill`` (submit -> first token, which is mostly
    waiting) the engine's own timeline says which wait it was:
    ``engine.queue``, ``engine.prep``, ``engine.first_wait``, one after
    the other, inside their parent."""
    import requests as requests_lib

    url = _serve_stub(_ChunkyEngine(timelines=True), 23650)
    header = trace.make_header()
    r = requests_lib.post(
        f'{url}/generate', json={'tokens': [[1, 2, 3]], 'max_new_tokens': 4},
        headers={trace.TRACE_HEADER: header}, timeout=30)
    assert r.status_code == 200
    tid = trace.parse_header(header)[0]
    body = requests_lib.get(f'{url}/debug/traces',
                            params={'trace_id': tid}, timeout=10).json()
    spans = body['traces'][0]['spans']
    prefill = next(s for s in spans if s['name'] == 'serve.prefill')
    kids = [s for s in spans if s['parent_id'] == prefill['span_id']]
    assert [s['name'] for s in kids] == ['engine.queue', 'engine.prep',
                                         'engine.first_wait']
    for s in kids:
        assert prefill['start'] <= s['start'] <= s['end'] <= prefill['end']
        assert s['attrs']['path'] == 'shared'
        assert s['attrs']['saved_tokens'] == 2
    for a, b in zip(kids, kids[1:]):
        assert a['end'] == pytest.approx(b['start'], abs=1e-6)
    lengths = [s['end'] - s['start'] for s in kids]
    assert lengths[0] >= 0.015 and lengths[2] >= 0.015
    assert 0.003 <= lengths[1] < 0.015
    assert sum(lengths) <= prefill['end'] - prefill['start'] + 1e-6
    assert 'engine.decode' not in [s['name'] for s in spans]


@pytest.mark.slow
def test_trace_probe_end_to_end(monkeypatch):
    """Acceptance (shared with `make verify`'s perf_probe --trace): a
    real tiny-model CPU replica under a streamed mixed-class loadgen
    pass yields closed, properly-nested traces covering queue-wait ->
    prefill -> decode -> stream-complete, non-empty TTFT buckets, and
    greedy byte parity traced vs untraced."""
    import importlib.util

    # Register the env keys trace_smoke writes directly, so monkeypatch
    # teardown restores the pre-test values for later tests.
    for key in ('SKYTPU_TRACE', 'SKYTPU_TRACE_SAMPLE',
                'SKYTPU_TRACE_RING'):
        monkeypatch.setenv(key, os.environ.get(key, '1'))
    root = pathlib.Path(__file__).parents[1]
    spec = importlib.util.spec_from_file_location(
        'perf_probe_for_test', root / 'tools' / 'perf_probe.py')
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    try:
        out = mod.trace_smoke()
    finally:
        trace.reset()  # the probe fills the process-global ring
    assert out['streamed_phase_traces'] >= 12
    assert out['ttft_observations'] >= 12


def test_trace_shared_trace_id_roots_do_not_cross_contaminate(traced):
    """Two concurrent requests joining the SAME inbound trace id (the
    traceparent model invites this) collect into per-root buckets: the
    first root to finalize must not steal the other's spans, and the
    slower root keeps its own phase breakdown."""
    h = trace.make_header()
    ctx_a = trace.start_trace('req.a', parent_header=h)
    ctx_b = trace.start_trace('req.b', parent_header=h)
    root_a = ctx_a.__enter__()
    trace.add_span('a.phase', root_a.start, root_a.start + 0.01)
    root_b = ctx_b.__enter__()
    trace.add_span('b.phase', root_b.start, root_b.start + 0.01)
    ctx_b.__exit__(None, None, None)  # B finalizes first
    trace.add_span('a.late', root_a.start, root_a.start + 0.02,
                   parent=root_a)  # A still collecting
    ctx_a.__exit__(None, None, None)
    records = {tuple(sorted(s['name'] for s in r['spans']))
               for r in trace.collect(include_exported=False, limit=10)}
    # collect() merges by trace id for display; check the raw records.
    raw = {tuple(sorted(s['name'] for s in r['spans']))
           for r in trace._TRACER.snapshot()}
    assert ('b.phase', 'req.b') in raw, raw
    assert ('a.late', 'a.phase', 'req.a') in raw, raw
    # And the merged view still shows every span exactly once.
    merged = [r for r in records if len(r) == 5]
    assert merged, records


def test_replica_debug_scrape_token_and_lb_debug_refusal(traced,
                                                         monkeypatch):
    """Multi-tenant hardening: with SKYTPU_METRICS_TOKEN set the
    replica's /metrics and /debug/traces require the bearer, and the
    tenant-facing load balancer never proxies /debug/* at all."""
    import asyncio
    import threading

    import requests as requests_lib
    from aiohttp import web

    from skypilot_tpu.serve.load_balancer import LoadBalancer
    from skypilot_tpu.serve import llm_server as llm_mod
    from skypilot_tpu.utils import common_utils

    server = llm_mod.LlmServer('tiny', max_len=64, engine='off')
    port = common_utils.find_free_port(23700)
    started = threading.Event()

    def run():
        loop = asyncio.new_event_loop()
        asyncio.set_event_loop(loop)
        runner = web.AppRunner(server.make_app())
        loop.run_until_complete(runner.setup())
        loop.run_until_complete(
            web.TCPSite(runner, '127.0.0.1', port).start())
        started.set()
        loop.run_forever()

    threading.Thread(target=run, daemon=True).start()
    assert started.wait(15)
    url = f'http://127.0.0.1:{port}'

    # Open by default...
    assert requests_lib.get(f'{url}/metrics', timeout=10).status_code \
        == 200
    assert requests_lib.get(f'{url}/debug/traces',
                            timeout=10).status_code == 200
    # ...locked once the scrape token is set.
    monkeypatch.setenv('SKYTPU_METRICS_TOKEN', 'scrape-only')
    for path in ('/metrics', '/debug/traces'):
        assert requests_lib.get(f'{url}{path}',
                                timeout=10).status_code == 401
        assert requests_lib.get(
            f'{url}{path}', timeout=10,
            headers={'Authorization': 'Bearer wrong'}).status_code == 401
        assert requests_lib.get(
            f'{url}{path}', timeout=10,
            headers={'Authorization':
                     'Bearer scrape-only'}).status_code == 200

    # The LB refuses to PROXY /debug/* before even selecting a replica;
    # the one exception is its OWN /debug/traces (the lb.request
    # fragments + cross-replica stitcher), behind the same scrape token.
    lb = LoadBalancer(port=common_utils.find_free_port(23750))
    lb.start_in_thread()
    try:
        lb_url = f'http://127.0.0.1:{lb.port}'
        r = requests_lib.get(f'{lb_url}/debug/blackbox', timeout=10)
        assert r.status_code == 403, r.text
        r = requests_lib.get(f'{lb_url}/debug/traces', timeout=10)
        assert r.status_code == 401, r.text  # token still set above
        r = requests_lib.get(
            f'{lb_url}/debug/traces', timeout=10,
            headers={'Authorization': 'Bearer scrape-only'})
        assert r.status_code == 200, r.text
        assert 'traces' in r.json() and 'tail' in r.json()
        monkeypatch.delenv('SKYTPU_METRICS_TOKEN')
        r = requests_lib.get(f'{lb_url}/debug/traces', timeout=10)
        assert r.status_code == 200, r.text  # unset token = open
    finally:
        lb.stop()


# -- tail-based retention (observability/trace.py) ---------------------------


@pytest.fixture()
def tailed(traced, monkeypatch, tmp_path):
    """Pure-tail configuration: head sampling off, baseline off, spool
    isolated — every trace rides the pending/verdict path and nothing
    is kept unless a verdict fires."""
    monkeypatch.setenv('SKYTPU_TRACE_SAMPLE', '0')
    monkeypatch.setenv('SKYTPU_TRACE_TAIL', '1')
    monkeypatch.setenv('SKYTPU_TRACE_TAIL_BASELINE_PER_MIN', '0')
    monkeypatch.setenv('SKYTPU_TRACE_EXPORT_DIR', str(tmp_path / 'spool'))
    yield tmp_path / 'spool'


def _finish(name='serve.generate', **attrs):
    with trace.start_trace(name, **attrs):
        pass


def test_tail_outcome_verdicts_keep_and_export(tailed):
    _finish(status=429)                      # shed
    _finish(status=504)                      # evicted
    _finish(status=500)                      # error
    _finish(resume=True)                     # resumed
    _finish(status=200)                      # boring -> pending
    # Client hang-ups are NOT server errors: a disconnect storm must
    # not rotate real keeps out of the bounded ring.
    _finish(error='CancelledError')          # -> pending, not 'error'
    stats = trace.tail_stats()
    assert stats['kept'] == 4 and stats['pending'] == 2
    assert stats['verdicts'] == {'shed': 1, 'evicted': 1, 'error': 1,
                                 'resumed': 1}
    kept = trace.collect(include_exported=False, retained_only=True,
                         limit=10)
    assert {t['retained'] for t in kept} == {'shed', 'evicted', 'error',
                                             'resumed'}
    # Durable: every keep landed as a keep-* spool file (via the
    # background writer — drained explicitly here), none of the
    # pending/boring ones did.
    assert trace.flush_keep_exports()
    names = sorted(p.name for p in tailed.glob('*.json'))
    assert len(names) == 4 and all(n.startswith('keep-') for n in names)
    # The ring is EMPTY (nothing head-sampled), yet fetch-by-id works
    # through the retained store.
    tid = kept[0]['trace_id']
    assert trace.collect(trace_id=tid, include_exported=False,
                         limit=5)[0]['trace_id'] == tid


def test_tail_threshold_flags_per_class(tailed, monkeypatch):
    monkeypatch.setenv('SKYTPU_TRACE_TAIL_LATENCY_MS',
                       'interactive:600000,batch:0.0001')
    _finish(qos_class='interactive', status=200)   # far under its bar
    _finish(qos_class='batch', status=200)         # over its 0.1us bar
    stats = trace.tail_stats()
    assert stats['verdicts'] == {'slow': 1}
    kept = trace.collect(include_exported=False, retained_only=True)
    assert kept[0]['attrs']['qos_class'] == 'batch'
    th = trace.tail_thresholds()
    assert th['batch']['latency'] == {'ms': 0.0001, 'source': 'flag'}
    # Bare-number form applies to every class.
    monkeypatch.setenv('SKYTPU_TRACE_TAIL_LATENCY_MS', '0.0001')
    _finish(qos_class='interactive', status=200)
    assert trace.tail_stats()['verdicts']['slow'] == 2


def test_tail_auto_threshold_derivation(tailed):
    store = trace._TAIL
    rec = lambda ms, **attrs: {  # noqa: E731 — local record factory
        'trace_id': __import__('uuid').uuid4().hex, 'name': 'g',
        'start': time.time(), 'duration_ms': ms,
        'attrs': {'qos_class': 'standard', 'status': 200, **attrs},
        'spans': []}
    # Below MIN_WINDOW samples: no auto threshold, nothing kept.
    for _ in range(store.MIN_WINDOW - 1):
        assert store.evaluate(rec(10.0), sampled=False) is None
    assert trace.tail_thresholds().get('standard') is None
    # Warm window (p95 ~= 10ms): threshold 2x p95; a 10x outlier keeps,
    # a nominal request still parks.
    store.evaluate(rec(10.0), sampled=False)
    th = trace.tail_thresholds()['standard']['latency']
    assert th['source'] == 'auto' and 15.0 <= th['ms'] <= 25.0
    assert store.evaluate(rec(100.0), sampled=False) == 'slow'
    assert store.evaluate(rec(11.0), sampled=False) is None
    # TTFT rides its own window/threshold.
    for _ in range(store.MIN_WINDOW):
        store.evaluate(rec(10.0, ttft_ms=5.0), sampled=False)
    assert store.evaluate(rec(10.0, ttft_ms=500.0),
                          sampled=False) == 'slow_ttft'


def test_tail_pending_park_retain_promotion(tailed):
    with trace.start_trace('serve.generate', status=200) as root:
        tid = root.trace_id
    assert trace.tail_stats()['pending'] == 1
    assert trace.collect(trace_id=tid, include_exported=False) == []
    # Unknown verdicts clamp to 'propagated' (the bounded vocabulary);
    # prefix retain works past 8 chars.
    assert trace.retain(  # skylint: allow-verdict(tests the clamp)
        tid[:12], 'not-a-verdict') == 1
    assert trace.tail_stats()['pending'] == 0
    got = trace.collect(trace_id=tid, include_exported=False,
                        retained_only=True)
    assert got and got[0]['retained'] == 'propagated'
    assert trace.flush_keep_exports()
    assert any(p.name.startswith('keep-')
               for p in tailed.glob('*.json'))
    # Idempotent-ish: nothing left to promote.
    assert trace.retain(tid, 'propagated') == 0
    # debug_payload drives the same promotion (the LB's trailing fetch).
    with trace.start_trace('serve.generate', status=200) as root2:
        tid2 = root2.trace_id
    p = trace.debug_payload({'retain': tid2, 'verdict': 'propagated',
                             'trace_id': tid2, 'retained': '1'})
    assert p['retained_promoted'] == 1
    assert p['count'] == 1 and p['traces'][0]['retained'] == 'propagated'


def test_tail_pending_ttl_and_cap(tailed, monkeypatch):
    monkeypatch.setenv('SKYTPU_TRACE_TAIL_PENDING', '3')
    for _ in range(6):
        _finish(status=200)
    stats = trace.tail_stats()
    assert stats['pending'] == 3 and stats['expired'] == 3
    monkeypatch.setenv('SKYTPU_TRACE_TAIL_PENDING_S', '0.05')
    time.sleep(0.1)
    _finish(status=200)  # park triggers the TTL prune
    assert trace.tail_stats()['pending'] == 1


def test_tail_retained_ring_and_keep_rotation(tailed, monkeypatch):
    monkeypatch.setenv('SKYTPU_TRACE_TAIL_RING', '4')
    monkeypatch.setenv('SKYTPU_TRACE_TAIL_KEEP', '3')
    monkeypatch.setenv('SKYTPU_TRACE_EXPORT', '1')
    monkeypatch.setenv('SKYTPU_TRACE_SAMPLE', '1')
    monkeypatch.setenv('SKYTPU_TRACE_EXPORT_KEEP', '2')
    for i in range(8):
        _finish(status=500)  # error: every one kept AND ring-exported
        time.sleep(0.002)    # distinct export-file timestamps
    # The retained ring itself is bounded (head-sampled kept records
    # additionally live in the 256-deep main ring, which is why the
    # assertion reads the store, not collect()).
    assert len(trace._TAIL.retained_snapshot()) == 4
    assert trace.flush_keep_exports()
    keeps = sorted(p.name for p in tailed.glob('keep-*.json'))
    plain = sorted(p.name for p in tailed.glob('[0-9]*.json'))
    # The two rotation budgets are independent: keep-* files never
    # count against the plain export budget or vice versa.
    assert len(keeps) == 3 and len(plain) == 2


def test_collect_slowest_ranks_retained_store_and_spool(tailed,
                                                       monkeypatch):
    """Satellite regression: ?slowest=1 must rank what retention kept —
    the in-process retained store AND the keep-* spool (another
    process's keep) — not just the head-sampled ring."""
    monkeypatch.setenv('SKYTPU_TRACE_SAMPLE', '1')
    _finish(name='fast.ring', status=200)  # in ring, boring, ~0ms
    # A retained slow trace that never entered the ring (tail path).
    monkeypatch.setenv('SKYTPU_TRACE_SAMPLE', '0')
    monkeypatch.setenv('SKYTPU_TRACE_TAIL_LATENCY_MS', '10')
    with trace.start_trace('slow.retained', status=200):
        time.sleep(0.05)  # genuinely slower than the ring trace
    monkeypatch.delenv('SKYTPU_TRACE_TAIL_LATENCY_MS')
    # A foreign process's keep file, slower than everything local.
    t0 = time.time()
    foreign = {'trace_id': 'f' * 32, 'name': 'slow.foreign',
               'start': t0 - 10, 'duration_ms': 9999.0, 'attrs': {},
               'retained': 'slow',
               'spans': [{'name': 'slow.foreign', 'span_id': 'a' * 16,
                          'parent_id': None, 'start': t0 - 10,
                          'end': t0 - 0.001}]}
    tailed.mkdir(parents=True, exist_ok=True)
    (tailed / f'keep-{int((t0 - 10) * 1000):013d}-{"f" * 12}-99.json'
     ).write_text(json.dumps(foreign))
    out = trace.collect(limit=3, slowest_first=True)
    assert [t['name'] for t in out][:2] == ['slow.foreign',
                                            'slow.retained']
    assert out[0]['retained'] == 'slow'


def test_spool_merge_torn_duplicate_and_rotation_race(tailed,
                                                      monkeypatch):
    """Satellite: collect() over a spool with torn/partial files,
    duplicate trace ids (ring + disk), and keep-rotation racing the
    reader — no exception, no dropped good records, no double-counted
    spans."""
    import threading
    import uuid as uuid_lib
    monkeypatch.setenv('SKYTPU_TRACE_SAMPLE', '1')
    monkeypatch.setenv('SKYTPU_TRACE_EXPORT', '1')
    with trace.start_trace('dup.root', status=200) as root:
        tid = root.trace_id
    # The same record is now in the ring AND on disk: spans dedup by id.
    merged = trace.collect(trace_id=tid, limit=5)
    assert len(merged) == 1 and len(merged[0]['spans']) == 1
    # Torn tail (truncated json) + partial (valid json, no trace_id) +
    # foreign garbage are all invisible.
    (tailed / f'{int(time.time() * 1000):013d}-{"a" * 12}-1.json'
     ).write_text('{"trace_id": "a')
    (tailed / f'{int(time.time() * 1000):013d}-{"b" * 12}-1.json'
     ).write_text('{"spans": []}')
    (tailed / 'not-a-trace.json').write_text('[]')
    assert [t['trace_id'] for t in trace.collect(trace_id=tid, limit=5)
            ] == [tid]
    # Keep-rotation racing a reader: a writer thread hammers keeps with
    # a tiny budget (each write rotates older keep files away) while
    # the reader loops collect(); unreadable/vanishing files skip.
    monkeypatch.setenv('SKYTPU_TRACE_TAIL_KEEP', '2')
    stop = threading.Event()

    def writer():
        i = 0
        while not stop.is_set() and i < 200:
            rec = {'trace_id': uuid_lib.uuid4().hex, 'name': 'w',
                   'start': time.time(), 'duration_ms': 1.0,
                   'attrs': {}, 'spans': []}
            trace._export(rec, keep=True)
            i += 1

    th = threading.Thread(target=writer)
    th.start()
    try:
        for _ in range(50):
            out = trace.collect(limit=20, slowest_first=True)
            assert all(t.get('trace_id') for t in out)
    finally:
        stop.set()
        th.join(timeout=30)


def test_tail_ambient_verdicts_slo_and_baseline(tailed, monkeypatch):
    # slo_breach: a firing rule in this process keeps the journey.
    from skypilot_tpu.observability import slo as slo_mod
    monkeypatch.setattr(slo_mod, 'enabled', lambda: True)
    monkeypatch.setattr(slo_mod, 'firing_rules',
                        lambda: ['serve.ttft_p99'])
    _finish(status=200)
    assert trace.tail_stats()['verdicts'] == {'slo_breach': 1}
    monkeypatch.setattr(slo_mod, 'firing_rules', lambda: [])
    # baseline: bounded budget per minute.
    monkeypatch.setenv('SKYTPU_TRACE_TAIL_BASELINE_PER_MIN', '2')
    for _ in range(5):
        _finish(status=200)
    stats = trace.tail_stats()
    assert stats['verdicts'].get('baseline') == 2
    assert stats['pending'] == 3


def test_keep_hooks_fire_and_remove(tailed):
    seen = []
    hook = lambda record, verdict: seen.append(  # noqa: E731
        (record['trace_id'], verdict))
    trace.add_keep_hook(hook)
    try:
        with trace.start_trace('serve.generate', status=500) as root:
            tid = root.trace_id
        assert seen == [(tid, 'error')]
    finally:
        trace.remove_keep_hook(hook)
    _finish(status=500)
    assert len(seen) == 1  # removed hook stays silent
    assert trace.retained_ids(limit=4)[0] == \
        trace.collect(retained_only=True, include_exported=False,
                      limit=1)[0]['trace_id']


def test_verdict_for_status_and_registry_bounds():
    assert trace.verdict_for_status(429) == 'shed'
    assert trace.verdict_for_status(504) == 'evicted'
    assert trace.verdict_for_status(500) == 'error'
    assert trace.verdict_for_status(200) is None
    assert trace.verdict_for_status(400) is None  # client error: boring
    for v in ('slow', 'slow_ttft', 'error', 'shed', 'evicted',
              'resumed', 'slo_breach', 'recompile_storm', 'baseline',
              'propagated'):
        assert v in trace.VERDICT_NAMES


def test_phase_breakdown_and_autopsy_payload(tailed, monkeypatch):
    t0 = 1000.0
    spans = [
        {'name': 'lb.request', 'span_id': 'r' * 16, 'parent_id': None,
         'start': t0, 'end': t0 + 1.0},
        {'name': 'qos.queue_wait', 'span_id': 'q' * 16,
         'parent_id': 'r' * 16, 'start': t0, 'end': t0 + 0.2},
        {'name': 'serve.prefill', 'span_id': 'p' * 16,
         'parent_id': 'r' * 16, 'start': t0 + 0.2, 'end': t0 + 0.5},
        {'name': 'serve.decode', 'span_id': 'd' * 16,
         'parent_id': 'r' * 16, 'start': t0 + 0.5, 'end': t0 + 0.8},
        {'name': 'serve.stream', 'span_id': 's' * 16,
         'parent_id': 'r' * 16, 'start': t0 + 0.5, 'end': t0 + 0.9},
        {'name': 'lb.handoff.fetch', 'span_id': 'h' * 16,
         'parent_id': 'r' * 16, 'start': t0 + 0.8, 'end': t0 + 0.85},
    ]
    tr = {'trace_id': 'c' * 32, 'name': 'lb.request', 'start': t0,
          'duration_ms': 1000.0, 'attrs': {'qos_class': 'standard'},
          'retained': 'slow', 'spans': spans}
    b = trace.phase_breakdown(tr)
    assert b['queue'] == 200.0 and b['prefill'] == 300.0
    assert b['decode'] == 300.0 and b['handoff'] == 50.0
    assert b['stream'] == 100.0  # stream minus decode overlap
    assert b['total'] == 1000.0 and b['other'] == 50.0
    a = trace.autopsy(tr)
    assert a['retained'] == 'slow' and a['qos_class'] == 'standard'
    # Baseline: mean over recent ring peers of the class.
    monkeypatch.setenv('SKYTPU_TRACE_SAMPLE', '1')
    _finish(qos_class='standard', status=200)
    base = trace.class_baseline('standard')
    assert base and base['n'] >= 1 and 'total' in base


def test_exemplar_store_and_openmetrics_exposition(tailed, monkeypatch):
    from skypilot_tpu.server import metrics
    metrics.reset_exemplars_for_testing()
    tid = 'e' * 32
    metrics.observe_serving('skytpu_serve_ttft_seconds', 0.3,
                            trace_id=tid, qos_class='batch')
    metrics.observe_serving('skytpu_serve_ttft_seconds', 4.0,
                            trace_id='f' * 32, qos_class='batch')
    metrics.observe_serving('skytpu_serve_queue_wait_seconds', 0.01,
                            qos_class='interactive')  # untraced: no ex.
    p = metrics.exemplars_payload()
    assert p['count'] == 2
    by_le = {e['le']: e for e in p['exemplars']}
    assert by_le[0.5]['trace_id'] == tid
    assert by_le[5.0]['trace_id'] == 'f' * 32
    assert all(e['metric'] == 'skytpu_serve_ttft_seconds'
               for e in p['exemplars'])
    # Newest observation wins a bucket.
    metrics.observe_serving('skytpu_serve_ttft_seconds', 0.31,
                            trace_id='9' * 32, qos_class='batch')
    assert {e['le']: e for e in metrics.exemplars_payload()['exemplars']
            }[0.5]['trace_id'] == '9' * 32
    # The OpenMetrics exposition carries the exemplar on bucket lines.
    if metrics.openmetrics_available():
        text = metrics.render_serving(openmetrics=True).decode()
        assert any('# {trace_id="' in line
                   for line in text.splitlines()
                   if line.startswith('skytpu_serve_ttft_seconds_bucket'))
    # Retention gauges render from tail_stats.
    _finish(status=500)
    text = metrics.render_serving().decode()
    assert 'skytpu_trace_retained_total{verdict="error"} 1.0' in text
    metrics.reset_exemplars_for_testing()
