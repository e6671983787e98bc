"""Continuous-batching engine tests (models/engine.py).

The contract mirrors JetStream's slot server: requests prefill into free
slots of one persistent decode batch; every request's output must be
EXACTLY its solo greedy generation (generate() is the oracle, itself
parity-tested against the full re-forward in test_generate.py) no matter
when it was admitted, which slot it landed in, or what junk the freed
slots around it are decoding.
"""
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu.models import engine as engine_lib
from skypilot_tpu.models import generate, llama


@pytest.fixture(scope='module')
def tiny():
    cfg = llama.TINY
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


@pytest.fixture(scope='module')
def tiny_moe():
    # High capacity factor => no token ever dropped in either the solo or
    # the slot-batched call, so parity is exact (same reasoning as
    # test_generate.py's tiny_moe).
    cfg = dataclasses.replace(llama.MOE_TINY, expert_capacity_factor=4.0)
    params = llama.init_params(jax.random.PRNGKey(7), cfg)
    return cfg, params


def _solo(params, cfg, row, n, max_len=64):
    out = generate.generate(params, cfg, jnp.asarray([row], jnp.int32),
                            max_new_tokens=n, max_len=max_len)
    return np.asarray(out[0]).tolist()


def _mk(params, cfg, **kw):
    kw.setdefault('slots', 4)
    kw.setdefault('max_len', 64)
    kw.setdefault('chunk_steps', 4)
    eng = engine_lib.ContinuousEngine(params, cfg, **kw)
    eng.start()
    return eng


def test_engine_greedy_matches_generate(tiny):
    cfg, params = tiny
    eng = _mk(params, cfg)
    try:
        rows = [[5, 6, 7], [8, 9, 10, 11, 12], [13, 14],
                [15, 16, 17, 18], [19, 20, 21]]  # > slots: forces reuse
        futs = [eng.submit(r, 6) for r in rows]
        for row, fut in zip(rows, futs):
            assert fut.result(timeout=120) == _solo(params, cfg, row, 6), row
        stats = eng.stats()
        assert stats['prefills'] == len(rows)
        assert stats['active_slots'] == 0
        assert stats['tokens_emitted'] >= 6 * len(rows)
    finally:
        eng.stop()


def test_engine_mid_stream_admission(tiny):
    """A request admitted while another is mid-decode must not perturb
    either one — the defining continuous-batching property."""
    cfg, params = tiny
    eng = _mk(params, cfg, chunk_steps=2)
    try:
        long_row = [3, 4, 5, 6]
        f1 = eng.submit(long_row, 20)
        deadline = time.time() + 60
        while eng.chunks_run < 1 and time.time() < deadline:
            time.sleep(0.01)
        assert eng.chunks_run >= 1, 'engine never started decoding'
        assert not f1.done()
        late_row = [9, 8, 7]
        f2 = eng.submit(late_row, 4)
        assert f2.result(timeout=120) == _solo(params, cfg, late_row, 4)
        assert f1.result(timeout=120) == _solo(params, cfg, long_row, 20)
    finally:
        eng.stop()


def test_engine_slot_reuse_resets_cache_row(tiny):
    """With ONE slot, the second request reuses the first's slot; a stale
    length/cache row would corrupt it."""
    cfg, params = tiny
    eng = _mk(params, cfg, slots=1)
    try:
        a = eng.submit([1, 2, 3], 5)
        assert a.result(timeout=120) == _solo(params, cfg, [1, 2, 3], 5)
        b = eng.submit([40, 41, 42, 43, 44, 45], 7)
        assert b.result(timeout=120) == _solo(
            params, cfg, [40, 41, 42, 43, 44, 45], 7)
    finally:
        eng.stop()


def test_engine_single_token_request_never_occupies_slot(tiny):
    cfg, params = tiny
    eng = _mk(params, cfg, slots=1)
    try:
        f = eng.submit([2, 3, 4], 1)
        assert f.result(timeout=120) == _solo(params, cfg, [2, 3, 4], 1)
        assert eng.stats()['active_slots'] == 0
        assert eng.stats()['chunks_run'] == 0  # resolved at prefill
    finally:
        eng.stop()


def test_engine_moe_junk_slots_do_not_consume_expert_capacity(tiny_moe):
    """MoE is the one cross-row coupling (shared expert capacity): freed
    slots keep decoding junk, and that junk must be masked out of routing
    (forward_cached active_rows) or it displaces real tokens."""
    cfg, params = tiny_moe
    eng = _mk(params, cfg, max_len=32)
    try:
        # Warm the engine so several slots hold junk from finished work.
        warm = [eng.submit([i + 1, i + 2], 3) for i in range(4)]
        for f in warm:
            f.result(timeout=120)
        row = [11, 12, 13, 14]
        got = eng.submit(row, 5).result(timeout=120)
        assert got == _solo(params, cfg, row, 5, max_len=32)
    finally:
        eng.stop()


def test_engine_tensor_parallel_matches_single_device(tiny):
    """TP-sharded serving (mesh tensor=2): weights/KV shard over heads,
    every engine fn compiles SPMD, and outputs still match the solo
    single-device generation."""
    from skypilot_tpu.parallel import mesh as mesh_lib

    cfg, params = tiny
    mesh = mesh_lib.build_mesh(mesh_lib.MeshSpec(fsdp=1, tensor=2),
                               devices=jax.devices()[:2])
    eng = _mk(params, cfg, mesh=mesh)
    try:
        rows = [[5, 6, 7], [8, 9, 10, 11, 12], [13, 14]]
        futs = [eng.submit(r, 6) for r in rows]
        for row, fut in zip(rows, futs):
            assert fut.result(timeout=120) == _solo(params, cfg, row, 6), row
    finally:
        eng.stop()


def test_engine_tp_with_data_axis(tiny):
    """data=2 x tensor=2: the slot (batch) axis itself shards over the
    mesh; scatter-insert and per-row decode must still be exact.

    The oracle runs over the SAME tensor-sharded params as the engine
    (partition-faithful): TP splits the matmul reductions, and at bf16
    a reduction-order delta legitimately flips greedy argmax near-ties
    (diagnosed on this seed: row [1, 2]'s 5th token sits on a 0.0096
    logit gap, below bf16 resolution — a single-device oracle picks the
    other side). Slot-sharding/scatter bugs still fail this test: they
    corrupt rows outright, not just near-ties."""
    from skypilot_tpu.models import quantization as quant_lib
    from skypilot_tpu.parallel import mesh as mesh_lib
    from skypilot_tpu.parallel import sharding as sharding_lib

    cfg, params = tiny
    mesh = mesh_lib.build_mesh(mesh_lib.MeshSpec(data=2, fsdp=1, tensor=2),
                               devices=jax.devices()[:4])
    sharded = quant_lib.shard_params(params, cfg, mesh,
                                     sharding_lib.ShardingRules())
    eng = _mk(params, cfg, mesh=mesh)
    try:
        rows = [[5, 6, 7], [9, 8, 7, 6], [1, 2], [3, 4, 5, 6, 7]]
        futs = [eng.submit(r, 5) for r in rows]
        for row, fut in zip(rows, futs):
            assert fut.result(timeout=120) == _solo(sharded, cfg, row, 5), \
                row
    finally:
        eng.stop()


def test_engine_tp_quantized_weights(tiny):
    """int8 weight-only quantized tree under TP: q8 codes shard like the
    original weight, scales shard with their output channels."""
    from skypilot_tpu.models import quantization as quant_lib
    from skypilot_tpu.parallel import mesh as mesh_lib

    cfg, params = tiny
    q = quant_lib.quantize_params(params)
    mesh = mesh_lib.build_mesh(mesh_lib.MeshSpec(fsdp=1, tensor=2),
                               devices=jax.devices()[:2])
    eng = _mk(q, cfg, mesh=mesh)
    try:
        row = [7, 8, 9, 10]
        got = eng.submit(row, 6).result(timeout=120)
        # Oracle: the same quantized tree, single device.
        want = np.asarray(generate.generate(
            q, cfg, jnp.asarray([row], jnp.int32), max_new_tokens=6,
            max_len=64)[0]).tolist()
        assert got == want
    finally:
        eng.stop()


def test_server_tp_quantized_params_born_sharded(tiny):
    """LlmServer --tp 2 --quantize int8: weights are initialized and
    quantized SHARDED (never materialized whole on one device), both
    request paths serve the same resident tree, and generation works."""
    from skypilot_tpu.serve import llm_server as llm_mod

    cfg, _ = tiny
    server = llm_mod.LlmServer('tiny', max_len=64, tp=2,
                               quantize='int8', engine='continuous')
    try:
        q8 = server.params['layers']['wq']['q8']
        assert len(q8.sharding.device_set) == 2, q8.sharding
        assert server.params is server.engine.params
        out = server.engine.submit([5, 6, 7], 4).result(timeout=120)
        assert len(out) == 4
    finally:
        server.engine.stop()


def test_engine_kv_int8_matches_generate_kv_int8(tiny):
    """Engine with the int8 KV cache: same quantization recipe at write
    time as generate(kv_quantize=True), so outputs are exactly equal —
    slot insertion scatters the scale planes alongside the codes."""
    cfg, params = tiny
    eng = _mk(params, cfg, kv_quantize=True)
    try:
        rows = [[5, 6, 7], [8, 9, 10, 11], [13, 14]]
        futs = [eng.submit(r, 6) for r in rows]
        for row, fut in zip(rows, futs):
            want = np.asarray(generate.generate(
                params, cfg, jnp.asarray([row], jnp.int32),
                max_new_tokens=6, max_len=64,
                kv_quantize=True)[0]).tolist()
            assert fut.result(timeout=120) == want, row
        assert eng.stats()['kv_cache'] == 'int8'
    finally:
        eng.stop()


def test_engine_kv_int8_tp(tiny):
    """int8 KV + tensor parallelism: scale planes shard with their
    kv_heads."""
    from skypilot_tpu.parallel import mesh as mesh_lib

    cfg, params = tiny
    mesh = mesh_lib.build_mesh(mesh_lib.MeshSpec(fsdp=1, tensor=2),
                               devices=jax.devices()[:2])
    eng = _mk(params, cfg, mesh=mesh, kv_quantize=True)
    try:
        row = [3, 4, 5, 6]
        want = np.asarray(generate.generate(
            params, cfg, jnp.asarray([row], jnp.int32), max_new_tokens=5,
            max_len=64, kv_quantize=True)[0]).tolist()
        assert eng.submit(row, 5).result(timeout=120) == want
    finally:
        eng.stop()


def test_engine_temperature_sampling_runs(tiny):
    cfg, params = tiny
    eng = _mk(params, cfg)
    try:
        out = eng.submit([4, 5, 6], 8, temperature=1.0).result(timeout=120)
        assert len(out) == 8
        assert all(0 <= t < cfg.vocab_size for t in out)
    finally:
        eng.stop()


def test_engine_survives_device_failure(tiny):
    """A failed dispatch (OOM, wedged relay) must fail the in-flight
    waiters with the real error, rebuild device state (the donated cache
    may be consumed), and keep serving new requests."""
    cfg, params = tiny
    eng = _mk(params, cfg)
    try:
        ok = eng.submit([1, 2, 3], 4)
        assert ok.result(timeout=120) == _solo(params, cfg, [1, 2, 3], 4)
        eng._cache = None  # sabotage the device state
        import concurrent.futures as cf
        with pytest.raises(Exception) as excinfo:
            eng.submit([4, 5, 6], 4).result(timeout=120)
        # The future must carry the REAL failure promptly — a mid-prefill
        # request dropped from every tracking structure would only ever
        # "fail" by result() timeout.
        assert not isinstance(excinfo.value, cf.TimeoutError)
        after = eng.submit([7, 8, 9], 4)
        assert after.result(timeout=120) == _solo(params, cfg, [7, 8, 9], 4)
    finally:
        eng.stop()


def test_engine_streaming_callback(tiny):
    """on_tokens fires incrementally (first token, then per decode
    chunk) and the concatenation equals the future's final result."""
    cfg, params = tiny
    eng = _mk(params, cfg, chunk_steps=2)
    try:
        chunks = []
        fut = eng.submit([5, 6, 7], 7, on_tokens=chunks.append)
        final = fut.result(timeout=120)
        assert final == _solo(params, cfg, [5, 6, 7], 7)
        assert [t for c in chunks for t in c] == final
        assert len(chunks) >= 3  # 1 (prefill) + ceil(6/2) chunk batches
    finally:
        eng.stop()


def test_engine_raising_callback_isolated(tiny):
    """A raising on_tokens (dead streaming client) must lose only its
    own stream — both its future AND other concurrent requests still
    complete with correct tokens."""
    cfg, params = tiny
    eng = _mk(params, cfg, chunk_steps=2)
    try:
        def boom(_):
            raise RuntimeError('client went away')

        bad = eng.submit([1, 2, 3], 6, on_tokens=boom)
        good_chunks = []
        good = eng.submit([9, 8, 7], 6, on_tokens=good_chunks.append)
        assert good.result(timeout=120) == _solo(params, cfg, [9, 8, 7], 6)
        assert bad.result(timeout=120) == _solo(params, cfg, [1, 2, 3], 6)
        assert [t for c in good_chunks for t in c] == good.result()
    finally:
        eng.stop()


def test_llm_server_http_streaming(tiny):
    """NDJSON streaming over HTTP: per-chunk lines whose concatenation
    equals the non-streamed response, terminated by {'done': true};
    stream without the engine is a 400."""
    import json as json_lib
    import threading

    import requests as requests_lib
    from aiohttp import web

    from skypilot_tpu.models.engine import ContinuousEngine
    from skypilot_tpu.serve import llm_server as llm_mod
    from skypilot_tpu.utils import common_utils

    cfg, params = tiny
    server = llm_mod.LlmServer('tiny', max_len=64, engine='continuous')
    server.params = params
    server.engine.stop()
    server.engine = ContinuousEngine(params, cfg, slots=4, max_len=64,
                                     chunk_steps=2)
    port = common_utils.find_free_port(21600)
    started = threading.Event()

    def run():
        import asyncio
        loop = asyncio.new_event_loop()
        asyncio.set_event_loop(loop)
        runner = web.AppRunner(server.make_app())
        loop.run_until_complete(runner.setup())
        site = web.TCPSite(runner, '127.0.0.1', port)
        loop.run_until_complete(site.start())
        started.set()
        loop.run_forever()

    threading.Thread(target=run, daemon=True).start()
    assert started.wait(10)

    row = [5, 6, 7, 8]
    r = requests_lib.post(
        f'http://127.0.0.1:{port}/generate',
        json={'tokens': [row], 'max_new_tokens': 7, 'stream': True},
        stream=True, timeout=180)
    assert r.status_code == 200
    lines = [json_lib.loads(ln) for ln in r.iter_lines() if ln.strip()]
    assert lines[-1] == {'done': True}
    toks = [t for ln in lines[:-1] for t in ln['tokens']]
    assert all(ln['row'] == 0 for ln in lines[:-1])
    assert len(lines) >= 4  # first + >=2 chunks + done
    assert toks == _solo(params, cfg, row, 7)

    # Seeded streaming is refused (determinism needs the window path).
    r2 = requests_lib.post(
        f'http://127.0.0.1:{port}/generate',
        json={'tokens': [row], 'max_new_tokens': 4, 'stream': True,
              'temperature': 1.0, 'seed': 3}, timeout=30)
    assert r2.status_code == 400
    server.engine.stop()


def test_engine_rejects_oversized_request(tiny):
    cfg, params = tiny
    eng = engine_lib.ContinuousEngine(params, cfg, slots=2, max_len=32)
    with pytest.raises(ValueError, match='max_len'):
        eng.submit([1] * 30, 8)


def test_prompt_bucket():
    assert engine_lib.prompt_bucket(1) == 16
    assert engine_lib.prompt_bucket(16) == 16
    assert engine_lib.prompt_bucket(17) == 32
    assert engine_lib.prompt_bucket(100) == 128


def test_llm_server_engine_http_roundtrip(tiny):
    """The serving replica with the engine on: concurrent mixed-length
    requests over HTTP all match their solo greedy generation, and
    /health exposes engine stats."""
    import concurrent.futures as cf
    import threading

    import requests as requests_lib
    from aiohttp import web

    from skypilot_tpu.serve import llm_server as llm_mod
    from skypilot_tpu.utils import common_utils

    cfg, params = tiny
    server = llm_mod.LlmServer('tiny', max_len=64, engine='continuous')
    server.params = params
    server.engine.params = params  # same weights as the oracle
    port = common_utils.find_free_port(21400)
    started = threading.Event()

    def run():
        import asyncio
        loop = asyncio.new_event_loop()
        asyncio.set_event_loop(loop)
        runner = web.AppRunner(server.make_app())
        loop.run_until_complete(runner.setup())
        site = web.TCPSite(runner, '127.0.0.1', port)
        loop.run_until_complete(site.start())
        started.set()
        loop.run_forever()

    threading.Thread(target=run, daemon=True).start()
    assert started.wait(10)

    prompts = [[5, 6, 7], [8, 9, 10, 11, 12], [13, 14], [15, 16, 17, 18]]

    def post(row):
        r = requests_lib.post(
            f'http://127.0.0.1:{port}/generate',
            json={'tokens': [row], 'max_new_tokens': 5}, timeout=180)
        assert r.status_code == 200, r.text
        return r.json()['tokens'][0]

    with cf.ThreadPoolExecutor(max_workers=4) as pool:
        results = list(pool.map(post, prompts))
    for row, got in zip(prompts, results):
        assert got == _solo(params, cfg, row, 5), row

    h = requests_lib.get(f'http://127.0.0.1:{port}/health',
                         timeout=10).json()
    assert h['engine']['prefills'] == len(prompts)
    assert h['engine']['tokens_emitted'] >= 5 * len(prompts)
    # Window-batch counters untouched: everything rode the engine.
    assert h['batches_served'] == 0

    # Seeded sampling bypasses the engine (determinism contract): same
    # seed twice => identical tokens, engine prefill count unchanged.
    def seeded():
        r = requests_lib.post(
            f'http://127.0.0.1:{port}/generate',
            json={'tokens': [[3, 4, 5]], 'max_new_tokens': 6,
                  'temperature': 1.0, 'seed': 7}, timeout=180)
        assert r.status_code == 200, r.text
        return r.json()['tokens'][0]

    s1, s2 = seeded(), seeded()
    assert s1 == s2
    h2 = requests_lib.get(f'http://127.0.0.1:{port}/health',
                          timeout=10).json()
    assert h2['engine']['prefills'] == len(prompts)
    assert h2['batches_served'] == 2
    server.engine.stop()


def test_sampling_top_k_one_is_greedy(tiny):
    """top_k=1 at any temperature collapses to argmax — the cheapest
    end-to-end check that the filter really constrains sampling."""
    cfg, params = tiny
    row = jnp.asarray([[5, 6, 7, 8]], jnp.int32)
    greedy = generate.generate(params, cfg, row, 6, max_len=64)
    sampled = generate.generate(params, cfg, row, 6, max_len=64,
                                temperature=1.5,
                                key=jax.random.PRNGKey(3), top_k=1)
    np.testing.assert_array_equal(np.asarray(greedy), np.asarray(sampled))


def test_sampling_top_p_tiny_is_greedy(tiny):
    cfg, params = tiny
    row = jnp.asarray([[9, 8, 7]], jnp.int32)
    greedy = generate.generate(params, cfg, row, 5, max_len=64)
    sampled = generate.generate(params, cfg, row, 5, max_len=64,
                                temperature=2.0,
                                key=jax.random.PRNGKey(4), top_p=1e-6)
    np.testing.assert_array_equal(np.asarray(greedy), np.asarray(sampled))


def test_sampling_top_k_restricts_support(tiny):
    """Every sampled first token must come from the prompt logits'
    top-k set."""
    from skypilot_tpu.models import sampling as sampling_lib

    cfg, params = tiny
    prompt = jnp.asarray([[3, 4, 5]], jnp.int32)
    cache = generate.init_cache(cfg, 1, 32)
    logits, _ = generate.forward_cached(params, prompt, cache, cfg)
    k = 5
    allowed = set(np.argsort(np.asarray(logits[0]))[-k:].tolist())
    for seed in range(20):
        tok = sampling_lib.sample(
            logits, jnp.asarray([2.0], jnp.float32),
            jax.random.PRNGKey(seed), jnp.asarray([k], jnp.int32),
            jnp.asarray([1.0], jnp.float32))
        assert int(tok[0]) in allowed


def test_engine_per_slot_sampling_mix(tiny):
    """One greedy request and one top-k sampled request share the decode
    batch; the greedy one must stay exactly greedy."""
    cfg, params = tiny
    eng = _mk(params, cfg, chunk_steps=2)
    try:
        g = eng.submit([5, 6, 7], 6)
        s = eng.submit([8, 9, 10], 6, temperature=1.0, top_k=8)
        assert g.result(timeout=120) == _solo(params, cfg, [5, 6, 7], 6)
        out = s.result(timeout=120)
        assert len(out) == 6
        assert all(0 <= t < cfg.vocab_size for t in out)
    finally:
        eng.stop()


def test_engine_stream_honors_top_k(tiny):
    """Streamed requests must apply sampling filters too: stream with
    top_k=1 equals the greedy stream token-for-token (the non-stream
    path already guarantees this; a dropped param would sample the full
    vocab)."""
    import json as json_lib
    import threading

    import requests as requests_lib
    from aiohttp import web

    from skypilot_tpu.serve import llm_server as llm_mod
    from skypilot_tpu.utils import common_utils

    cfg, params = tiny
    server = llm_mod.LlmServer('tiny', max_len=64, engine='continuous')
    server.params = params
    server.engine.params = params
    port = common_utils.find_free_port(21800)
    started = threading.Event()

    def run():
        import asyncio
        loop = asyncio.new_event_loop()
        asyncio.set_event_loop(loop)
        runner = web.AppRunner(server.make_app())
        loop.run_until_complete(runner.setup())
        site = web.TCPSite(runner, '127.0.0.1', port)
        loop.run_until_complete(site.start())
        started.set()
        loop.run_forever()

    threading.Thread(target=run, daemon=True).start()
    assert started.wait(10)

    row = [5, 6, 7, 8]

    def stream_tokens(extra):
        r = requests_lib.post(
            f'http://127.0.0.1:{port}/generate',
            json={'tokens': [row], 'max_new_tokens': 6, 'stream': True,
                  **extra}, stream=True, timeout=180)
        assert r.status_code == 200
        lines = [json_lib.loads(ln) for ln in r.iter_lines()
                 if ln.strip()]
        assert lines[-1] == {'done': True}, lines[-1]
        return [t for ln in lines[:-1] for t in ln['tokens']]

    greedy = stream_tokens({})
    topk1 = stream_tokens({'temperature': 1.7, 'top_k': 1})
    assert greedy == topk1 == _solo(params, cfg, row, 6)
    server.engine.stop()


def test_engine_eos_stops_early_and_frees_slot(tiny):
    """Generation ends at the stop id (inclusive) instead of burning
    max_new; the slot frees immediately."""
    cfg, params = tiny
    eng = _mk(params, cfg, chunk_steps=2)
    try:
        row = [5, 6, 7]
        solo = _solo(params, cfg, row, 10)
        eos = solo[3]  # known greedy 4th token
        got = eng.submit(row, 10, eos=eos).result(timeout=120)
        assert got == solo[:4]
        assert eng.stats()['active_slots'] == 0
        # Multi-id stop set, and eos-not-reached runs to max_new.
        got2 = eng.submit(row, 4, eos=[99999]).result(timeout=120)
        assert got2 == solo[:4]
    finally:
        eng.stop()


def test_engine_eos_on_first_token(tiny):
    """Prefill's sampled token itself being the stop id must resolve the
    request at drain time and free the already-occupied slot."""
    cfg, params = tiny
    eng = _mk(params, cfg, slots=1)
    try:
        row = [5, 6, 7]
        first = _solo(params, cfg, row, 1)[0]
        got = eng.submit(row, 10, eos=first).result(timeout=120)
        assert got == [first]
        assert eng.stats()['active_slots'] == 0
        # The in-flight chunk (dispatched before the drain resolved this
        # request) must NOT append post-eos tokens to the delivered list.
        time.sleep(1.0)
        assert got == [first]
        # The single slot is reusable immediately.
        other = [9, 8, 7]
        assert (eng.submit(other, 3).result(timeout=120)
                == _solo(params, cfg, other, 3))
    finally:
        eng.stop()


def test_llm_server_eos_token(tiny):
    """eos_token over HTTP: engine path, window path (engine off via
    seeded request), and the stream all truncate at the stop id."""
    import json as json_lib
    import threading

    import requests as requests_lib
    from aiohttp import web

    from skypilot_tpu.serve import llm_server as llm_mod
    from skypilot_tpu.utils import common_utils

    cfg, params = tiny
    server = llm_mod.LlmServer('tiny', max_len=64, engine='continuous')
    server.params = params
    server.engine.params = params
    port = common_utils.find_free_port(21900)
    started = threading.Event()

    def run():
        import asyncio
        loop = asyncio.new_event_loop()
        asyncio.set_event_loop(loop)
        runner = web.AppRunner(server.make_app())
        loop.run_until_complete(runner.setup())
        site = web.TCPSite(runner, '127.0.0.1', port)
        loop.run_until_complete(site.start())
        started.set()
        loop.run_forever()

    threading.Thread(target=run, daemon=True).start()
    assert started.wait(10)

    row = [5, 6, 7]
    solo = _solo(params, cfg, row, 10)
    eos = solo[3]
    url = f'http://127.0.0.1:{port}/generate'

    r = requests_lib.post(url, json={
        'tokens': [row], 'max_new_tokens': 10, 'eos_token': eos},
        timeout=180)
    assert r.json()['tokens'][0] == solo[:4]

    # Seeded => window path; greedy-equivalent via temperature 0 is not
    # seeded, so force the window path with a seed + temperature and
    # only check truncation semantics (ends with a stop id, shorter
    # than max_new OR exactly max_new without the id).
    r2 = requests_lib.post(url, json={
        'tokens': [row], 'max_new_tokens': 10, 'temperature': 1.0,
        'seed': 5, 'eos_token': list(range(0, 128))}, timeout=180)
    toks2 = r2.json()['tokens'][0]
    hits = [t for t in toks2 if t < 128]
    if len(toks2) < 10:
        assert toks2[-1] < 128 and len(hits) == 1
    else:
        assert not hits[:-1]

    sr = requests_lib.post(url, json={
        'tokens': [row], 'max_new_tokens': 10, 'stream': True,
        'eos_token': eos}, stream=True, timeout=180)
    lines = [json_lib.loads(ln) for ln in sr.iter_lines() if ln.strip()]
    assert lines[-1] == {'done': True}
    streamed = [t for ln in lines[:-1] for t in ln['tokens']]
    assert streamed == solo[:4]

    r3 = requests_lib.post(url, json={
        'tokens': [row], 'max_new_tokens': 4, 'eos_token': 'nope'},
        timeout=30)
    assert r3.status_code == 400
    server.engine.stop()


def test_engine_chunked_prefill_exact(tiny):
    """A prompt longer than prefill_chunk advances in chunks and still
    produces EXACTLY the solo greedy generation (positions/cache writes
    are identical to a monolithic prefill)."""
    cfg, params = tiny
    eng = _mk(params, cfg, prefill_chunk=8)
    try:
        long_row = list(range(1, 31))  # 30 tokens -> 4 chunks of <=8
        got = eng.submit(long_row, 6).result(timeout=120)
        assert got == _solo(params, cfg, long_row, 6)
        st = eng.stats()
        assert st['prefill_chunks'] >= 4
        assert st['prefilling'] == 0 and st['active_slots'] == 0
        # Short prompts still take the grouped path.
        short = [5, 6, 7]
        assert eng.submit(short, 4).result(timeout=120) == \
            _solo(params, cfg, short, 4)
    finally:
        eng.stop()


def test_engine_chunked_prefill_interleaves_with_decode(tiny):
    """Active slots keep decoding while a long prompt chunks in: the
    short request admitted first must finish well before the long one,
    and both stay exact."""
    cfg, params = tiny
    eng = _mk(params, cfg, prefill_chunk=4, chunk_steps=2)
    try:
        short = [9, 8, 7]
        f_short = eng.submit(short, 12)
        long_row = list(range(1, 41))  # 40 tokens -> 10 chunks
        f_long = eng.submit(long_row, 4)
        assert f_short.result(timeout=120) == _solo(params, cfg, short, 12)
        assert f_long.result(timeout=120) == _solo(params, cfg,
                                                   long_row, 4)
        assert eng.stats()['prefill_chunks'] >= 10
    finally:
        eng.stop()


def test_engine_chunked_prefill_parks_until_slot_frees(tiny):
    """With ONE slot busy, a finished long prefill parks and lands once
    the slot frees — no deadlock, exact output."""
    cfg, params = tiny
    eng = _mk(params, cfg, slots=1, prefill_chunk=4, chunk_steps=2)
    try:
        holder = [3, 4, 5]
        f1 = eng.submit(holder, 10)
        long_row = list(range(10, 30))
        f2 = eng.submit(long_row, 3)
        assert f1.result(timeout=120) == _solo(params, cfg, holder, 10)
        assert f2.result(timeout=120) == _solo(params, cfg, long_row, 3)
    finally:
        eng.stop()


def test_engine_chunked_prefill_disabled_for_moe(tiny_moe):
    """Per-call expert capacity makes chunked prefill route differently
    than the monolithic oracle — MoE configs must refuse it."""
    cfg, params = tiny_moe
    eng = engine_lib.ContinuousEngine(params, cfg, slots=2, max_len=32,
                                      prefill_chunk=8)
    assert eng.prefill_chunk == 0


def test_llm_server_graceful_drain(tmp_path):
    """SIGTERM mid-request: the replica flips /health to 503 (LB stops
    routing), refuses new /generate requests, lets the in-flight one
    finish with 200, and exits cleanly."""
    import os
    import signal
    import subprocess
    import sys
    import threading

    import requests as requests_lib

    from skypilot_tpu.utils import common_utils

    port = common_utils.find_free_port(22100)
    env = dict(os.environ, JAX_PLATFORMS='cpu', SKYTPU_LLM_CHUNK_STEPS='2')
    proc = subprocess.Popen(
        [sys.executable, '-m', 'skypilot_tpu.serve.llm_server',
         '--model', 'tiny', '--max-len', '256', '--host', '127.0.0.1',
         '--port', str(port)],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    try:
        deadline = time.time() + 60
        while time.time() < deadline:
            try:
                if requests_lib.get(f'http://127.0.0.1:{port}/health',
                                    timeout=2).status_code == 200:
                    break
            except requests_lib.RequestException:
                time.sleep(0.5)
        else:
            raise AssertionError('replica never became healthy')

        result = {}

        def long_request():
            # First request: pays jit compiles, giving SIGTERM a wide
            # in-flight window.
            r = requests_lib.post(
                f'http://127.0.0.1:{port}/generate',
                json={'tokens': [[5, 6, 7]], 'max_new_tokens': 64},
                timeout=120)
            result['status'] = r.status_code
            result['n'] = len(r.json().get('tokens', [[]])[0])

        t = threading.Thread(target=long_request)
        t.start()
        time.sleep(1.5)  # let it get in flight
        proc.send_signal(signal.SIGTERM)
        deadline = time.time() + 30
        while time.time() < deadline:
            try:
                h = requests_lib.get(f'http://127.0.0.1:{port}/health',
                                     timeout=2)
                if h.status_code == 503:
                    break
            except requests_lib.RequestException:
                break  # already exited after drain — also acceptable
            time.sleep(0.2)
        # New work is still ACCEPTED while draining (the LB keeps
        # routing here until its next probe cycle; refusing would drop
        # committed requests) — and the drain 503 body self-identifies.
        try:
            r2 = requests_lib.post(
                f'http://127.0.0.1:{port}/generate',
                json={'tokens': [[1, 2]], 'max_new_tokens': 2},
                timeout=30)
            assert r2.status_code == 200, r2.text
        except requests_lib.RequestException:
            pass  # exited already: drain completed first
        t.join(timeout=120)
        assert result.get('status') == 200, result
        assert result.get('n') == 64
        assert proc.wait(timeout=60) == 0
    finally:
        if proc.poll() is None:
            proc.kill()
