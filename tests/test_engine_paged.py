"""Paged (block-table) KV cache engine tests (r4 verdict Next #3).

Contract: identical outputs to the solo greedy oracle for every
admission pattern, with HBM measured
in BLOCKS — requests reserve only ceil((prompt+max_new)/block), the
pool can be sized below slots*max_len, and exhaustion queues admissions
instead of failing them.
"""
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


def _solo(params, cfg, row, n, max_len=64, **kw):
    out = generate.generate(params, cfg, jnp.asarray([row], jnp.int32),
                            max_new_tokens=n, max_len=max_len, **kw)
    return np.asarray(out[0]).tolist()


def _mk(params, cfg, **kw):
    kw.setdefault('slots', 4)
    kw.setdefault('max_len', 64)
    kw.setdefault('chunk_steps', 4)
    eng = engine_lib.ContinuousEngine(params, cfg, **kw)
    eng.start()
    return eng


def test_paged_greedy_matches_generate(tiny):
    # Blocks of 8 (test_engine.py runs the same rows at the default 16).
    cfg, params = tiny
    eng = _mk(params, cfg, kv_block=8)
    try:
        rows = [[5, 6, 7], [8, 9, 10, 11, 12], [13, 14],
                [15, 16, 17, 18], [19, 20, 21]]  # > slots: forces reuse
        futs = [eng.submit(r, 6) for r in rows]
        for row, fut in zip(rows, futs):
            assert fut.result(timeout=120) == _solo(params, cfg, row, 6), \
                row
        st = eng.stats()
        assert st['kv_layout'] == 'paged'
        # Every reservation returned to the pool.
        assert st['kv_blocks']['free'] == st['kv_blocks']['total'] - 1
    finally:
        eng.stop()


def test_paged_pool_smaller_than_slot_pinned_equivalent(tiny):
    """THE point of paging: a pool of 9 usable blocks (144 positions)
    serves 4 slots that slot-pinning would charge 4x64=256 positions
    for — mixed-length traffic completes exactly."""
    cfg, params = tiny
    eng = _mk(params, cfg, kv_blocks=10)  # 9 usable + junk sink
    try:
        rows = [[5, 6, 7], [8, 9, 10, 11, 12, 13, 14, 15], [16, 17],
                [18] * 20, [21, 22, 23]]
        futs = [eng.submit(r, 6) for r in rows]
        for row, fut in zip(rows, futs):
            assert fut.result(timeout=120) == _solo(params, cfg, row, 6), \
                row
        # After drain nothing is owned or referenced; full prompt
        # blocks stay behind as reclaimable prefix cache.
        kb = eng.stats()['kv_blocks']
        assert kb['owned'] == kb['shared'] == 0
        assert kb['free'] + kb['cached'] == 9
    finally:
        eng.stop()


def test_paged_backpressure_queues_when_pool_exhausted(tiny):
    """A pool with room for ONE request at a time still completes three
    — admission waits for completions to free blocks (no failure, no
    corruption)."""
    cfg, params = tiny
    # Each request: (3 prompt + 13 new) = 16 -> 1 block at block=16;
    # pool of 1 usable block forces strictly serial admission.
    eng = _mk(params, cfg, kv_blocks=2, chunk_steps=2)
    try:
        rows = [[5, 6, 7], [9, 8, 7], [11, 12, 13]]
        futs = [eng.submit(r, 13) for r in rows]
        for row, fut in zip(rows, futs):
            assert fut.result(timeout=180) == _solo(params, cfg, row, 13), \
                row
        assert eng.stats()['kv_blocks']['free'] == 1
        assert eng.stats()['peak_active_slots'] == 1  # serialized
    finally:
        eng.stop()


def test_paged_kv_int8_matches_kv_int8_oracle(tiny):
    cfg, params = tiny
    eng = _mk(params, cfg, kv_quantize=True)
    try:
        row = [7, 8, 9, 10]
        want = _solo(params, cfg, row, 6, kv_quantize=True)
        assert eng.submit(row, 6).result(timeout=120) == want
    finally:
        eng.stop()


def test_paged_single_token_request_reserves_no_blocks(tiny):
    cfg, params = tiny
    eng = _mk(params, cfg, kv_blocks=2)
    try:
        f = eng.submit([2, 3, 4], 1)
        assert f.result(timeout=120) == _solo(params, cfg, [2, 3, 4], 1)
        assert eng.stats()['kv_blocks']['free'] == 1  # untouched
    finally:
        eng.stop()


def test_paged_eos_frees_blocks_early(tiny):
    cfg, params = tiny
    eng = _mk(params, cfg, chunk_steps=2)
    try:
        row = [5, 6, 7]
        solo = _solo(params, cfg, row, 10)
        eos = solo[3]
        got = eng.submit(row, 10, eos=eos).result(timeout=120)
        assert got == solo[:4]
        deadline = time.time() + 30
        while eng.stats()['kv_blocks']['free'] != \
                eng.stats()['kv_blocks']['total'] - 1:
            assert time.time() < deadline, 'blocks never released'
            time.sleep(0.05)
    finally:
        eng.stop()


def test_paged_chunked_prefill_exact_and_parks_on_exhaustion(tiny):
    cfg, params = tiny
    eng = _mk(params, cfg, prefill_chunk=8, kv_blocks=4, chunk_steps=2)
    try:
        # Holder consumes 2 blocks (3 + 20 = 23 -> 2); the long prompt
        # needs 3 (34 + 4 = 38) and must PARK until the holder's blocks
        # free (pool has 3 usable).
        holder = [3, 4, 5]
        f1 = eng.submit(holder, 20)
        long_row = list(range(1, 35))  # 34 tokens -> 5 chunks
        f2 = eng.submit(long_row, 4)
        assert f1.result(timeout=180) == _solo(params, cfg, holder, 20)
        assert f2.result(timeout=180) == _solo(params, cfg, long_row, 4)
        assert eng.stats()['prefill_chunks'] >= 5
        kb = eng.stats()['kv_blocks']
        assert kb['owned'] == kb['shared'] == 0
        assert kb['free'] + kb['cached'] == 3
    finally:
        eng.stop()


def test_paged_moe_junk_slots_masked(tiny):
    """MoE routing masks junk rows through the paged forward too."""
    import dataclasses
    moe_cfg = dataclasses.replace(llama.MOE_TINY,
                                  expert_capacity_factor=4.0)
    moe_params = llama.init_params(jax.random.PRNGKey(7), moe_cfg)
    eng = _mk(moe_params, moe_cfg, max_len=32)
    try:
        warm = [eng.submit([i + 1, i + 2], 3) for i in range(4)]
        for f in warm:
            f.result(timeout=120)
        row = [11, 12, 13, 14]
        got = eng.submit(row, 5).result(timeout=120)
        assert got == _solo(moe_params, moe_cfg, row, 5, max_len=32)
    finally:
        eng.stop()


def test_paged_sampling_and_streaming(tiny):
    cfg, params = tiny
    eng = _mk(params, cfg)
    try:
        seen = []
        g = eng.submit([11, 12, 13], 8,
                       on_tokens=lambda t: seen.append(list(t)))
        s = eng.submit([8, 9, 10], 6, temperature=1.0, top_k=8)
        want = _solo(params, cfg, [11, 12, 13], 8)
        assert g.result(timeout=120) == want
        assert [t for c in seen for t in c] == want
        out = s.result(timeout=120)
        assert len(out) == 6 and all(0 <= t < cfg.vocab_size
                                     for t in out)
    finally:
        eng.stop()


def test_paged_freed_slot_junk_never_corrupts_reallocated_blocks(tiny):
    """Stale-table hazard (review finding): A (slot 0) and B (slot 1)
    complete; C admits into slot 0 holding B's released blocks (LIFO
    free list) while slot 1 keeps junk-decoding with a stale table
    pointing at those SAME blocks. Inactive rows must scatter to the
    junk sink, or slot 1 scribbles over C's live KV."""
    cfg, params = tiny
    eng = _mk(params, cfg, slots=2, chunk_steps=4)
    try:
        a = eng.submit([5, 6, 7], 6)
        b = eng.submit([8, 9, 10, 11], 8)
        assert a.result(timeout=120) == _solo(params, cfg, [5, 6, 7], 6)
        assert b.result(timeout=120) == _solo(params, cfg,
                                              [8, 9, 10, 11], 8)
        row = [21, 22, 23]
        got = eng.submit(row, 12).result(timeout=120)
        assert got == _solo(params, cfg, row, 12)
    finally:
        eng.stop()


def test_paged_tensor_parallel_matches_single_device(tiny):
    """Paged + TP: the pool shards on kv_heads over the tensor axis
    (tables replicated — scatter/gather index replicated dims only),
    and outputs still match the solo single-device generation."""
    from skypilot_tpu.parallel import mesh as mesh_lib
    cfg, params = tiny
    mesh = mesh_lib.build_mesh(mesh_lib.MeshSpec(fsdp=1, tensor=2),
                               devices=jax.devices()[:2])
    eng = _mk(params, cfg, slots=2, mesh=mesh)
    try:
        rows = [[5, 6, 7], [8, 9, 10, 11], [12, 13]]  # forces reuse
        futs = [eng.submit(r, 6) for r in rows]
        for row, fut in zip(rows, futs):
            assert fut.result(timeout=180) == _solo(params, cfg, row, 6)
        assert eng.stats()['kv_blocks']['free'] == \
            eng.stats()['kv_blocks']['total'] - 1
    finally:
        eng.stop()


def test_paged_tp_with_kv_int8(tiny):
    from skypilot_tpu.parallel import mesh as mesh_lib
    cfg, params = tiny
    mesh = mesh_lib.build_mesh(mesh_lib.MeshSpec(fsdp=1, tensor=2),
                               devices=jax.devices()[:2])
    eng = _mk(params, cfg, slots=2, mesh=mesh, kv_quantize=True)
    try:
        row = [7, 8, 9, 10]
        want = _solo(params, cfg, row, 6, kv_quantize=True)
        assert eng.submit(row, 6).result(timeout=180) == want
    finally:
        eng.stop()


def test_paged_gates():
    cfg = llama.TINY
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match='multiple of the'):
        engine_lib.ContinuousEngine(params, cfg, max_len=72, kv_block=16,
                                    slots=2)
    with pytest.raises(ValueError, match='one KV layout'):
        engine_lib.ContinuousEngine(params, cfg, kv_layout='banana')
    # A request bigger than the WHOLE pool is refused at submit — it
    # could never be admitted and would starve the queue behind it.
    eng = engine_lib.ContinuousEngine(params, cfg, slots=2, max_len=64,
                                      kv_blocks=2)
    with pytest.raises(ValueError, match='KV blocks'):
        eng.submit(list(range(10)), 10)  # 20 tokens -> 2 blocks > 1


def test_llm_server_paged_roundtrip(tiny):
    import threading

    import requests as requests_lib
    from aiohttp import web

    from skypilot_tpu.serve import llm_server as llm_mod
    from skypilot_tpu.utils import common_utils

    cfg, params = tiny
    server = llm_mod.LlmServer('tiny', max_len=64, engine='continuous')
    server.params = params
    server.engine.params = params
    port = common_utils.find_free_port(22000)
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
        json={'tokens': [row], 'max_new_tokens': 6}, timeout=180)
    assert r.status_code == 200
    assert r.json()['tokens'][0] == _solo(params, cfg, row, 6)
    h = requests_lib.get(f'http://127.0.0.1:{port}/health', timeout=30)
    eng_stats = h.json()['engine']
    assert eng_stats['kv_layout'] == 'paged'
    assert eng_stats['kv_blocks']['total'] > 0
    server.engine.stop()


# -- the decode step through ops/decode_attention.paged_decode --------------


@pytest.fixture(scope='module')
def wide():
    """head_dim 128: what ``paged_fits`` needs (a tiny preset's 16 is
    not a lane row). float32, so the kernel and the gather differ by
    accumulation order alone and greedy tokens agree."""
    import dataclasses
    cfg = dataclasses.replace(llama.TINY, head_dim=128, dtype=jnp.float32)
    return cfg, llama.init_params(jax.random.PRNGKey(0), cfg)


def _run_reuse_pattern(eng):
    """Two slots, three requests: the short one ends mid-run and the
    third is admitted into its slot while the long one still decodes —
    the freed slot's stale table names blocks that now belong to the
    third request."""
    rows = [[5, 6, 7], [8, 9, 10, 11, 12, 13, 14, 15, 16, 17, 18, 19,
                        20, 21, 22, 23, 24, 25], [26, 27]]
    futs = [eng.submit(r, n) for r, n in zip(rows, (3, 20, 9))]
    return [f.result(timeout=300) for f in futs]


@pytest.fixture()
def kernel_traces(monkeypatch):
    """Every trace of ``paged_decode``, as a list of its keywords. The
    path is chosen when the decode program is traced, and the engine's
    jits are module-level: drop what an earlier test compiled for these
    shapes, and what this one leaves."""
    from skypilot_tpu.ops import decode_attention
    traced = []
    real = decode_attention.paged_decode

    def counted(*args, **kw):
        traced.append(kw)
        return real(*args, **kw)

    monkeypatch.setattr(decode_attention, 'paged_decode', counted)
    engine_lib._jit_paged_chunk.clear_cache()
    yield traced
    engine_lib._jit_paged_chunk.clear_cache()


def test_paged_kernel_engine_matches_gather_engine(wide, kernel_traces,
                                                   monkeypatch):
    """The same requests through the engine, chunks of two steps, on
    the scatter + gather path and on the kernel that writes the step's
    row itself: the same tokens, and the same pool behind them (every
    block of every layer but the junk sinks, which only the scatter
    feeds with inactive rows; float32: 1e-4)."""
    from skypilot_tpu.models import paged as paged_lib
    from skypilot_tpu.ops import decode_attention
    cfg, params = wide
    eng = _mk(params, cfg, slots=2, chunk_steps=2)
    try:
        assert eng.stats()['decode_attention'] == 'gather'  # CPU
        want = _run_reuse_pattern(eng)
        want_cache = jax.tree.map(np.asarray, eng._cache)
    finally:
        eng.stop()
    assert not kernel_traces
    engine_lib._jit_paged_chunk.clear_cache()
    # Asked for by name: the kernel, in the interpreter.
    monkeypatch.setattr(decode_attention, 'PAGED_INTERPRET', True)
    eng = _mk(params, cfg, slots=2, chunk_steps=2)
    try:
        assert eng.stats()['decode_attention'] == 'paged_kernel'
        assert _run_reuse_pattern(eng) == want
        assert eng.stats()['failures'] == 0
        assert kernel_traces and all(kw['interpret']
                                     for kw in kernel_traces)
        cache = jax.tree.map(jnp.copy, eng._cache)
    finally:
        eng.stop()
    np.testing.assert_array_equal(cache.tables, want_cache.tables)
    np.testing.assert_array_equal(cache.lengths, want_cache.lengths)
    for got_plane, want_plane in ((cache.k, want_cache.k),
                                  (cache.v, want_cache.v)):
        assert np.asarray(got_plane[:, 1:]).any()
        np.testing.assert_allclose(np.asarray(got_plane[:, 1:]),
                                   want_plane[:, 1:], atol=1e-4)
    # One more step over the pool the run left behind (tables of
    # freed slots stale, lengths ragged), both ways: logits of order
    # 1 agree to 1e-4 in float32, and so do the pools they leave.
    toks = jnp.asarray([[3], [4]], jnp.int32)
    got, new = paged_lib.forward_paged(params, toks, cache, cfg)
    monkeypatch.setattr(decode_attention, 'PAGED_INTERPRET', False)
    ref, ref_new = paged_lib.forward_paged(params, toks, cache, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-4)
    np.testing.assert_allclose(np.asarray(new.k), np.asarray(ref_new.k),
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(new.v), np.asarray(ref_new.v),
                               atol=1e-4)


@pytest.mark.parametrize('quant,s,kernel', [
    (False, 1, False), (False, 1, True), (False, 5, False),
    (True, 1, False), (True, 5, False)],
    ids=['s1', 's1-kernel', 's5', 'int8-s1', 'int8-s5'])
def test_forward_paged_writes_its_rows_and_nothing_else(wide, monkeypatch,
                                                        quant, s, kernel):
    """The pools ride the layer scan whole and every layer writes its
    own rows of them in place: after a forward, each (layer, block, :,
    offset) row a live slot's positions name is new, and every other
    element of every plane (codes and scales) holds the bits it held.
    An inactive row, whose stale table names a live row's blocks, wrote
    the junk sink only: block 0, of layer 0."""
    from skypilot_tpu.models import paged as paged_lib
    from skypilot_tpu.ops import decode_attention
    cfg, params = wide
    monkeypatch.setattr(decode_attention, 'PAGED_INTERPRET', kernel)
    p, nb = 16, 12
    pool = paged_lib.init_pool(cfg, 4, 64, nb, p, quantize=quant)
    key = jax.random.PRNGKey(3)

    def noise(i, like):
        k = jax.random.fold_in(key, i)
        if like.dtype == jnp.int8:
            return jax.random.randint(k, like.shape, -127, 128, jnp.int8)
        return jax.random.uniform(k, like.shape, like.dtype, 0.01, 1.0)

    tables = np.asarray([[1, 2, 3, 0], [4, 5, 0, 0], [6, 7, 8, 9],
                         [4, 5, 0, 0]], np.int32)  # row 3: row 1's, stale
    lengths = np.asarray([30, 14, 44, 20], np.int32)
    active = np.asarray([True, True, True, False])
    planes = {n: noise(i, getattr(pool, n)) for i, n in enumerate(
        ('k', 'v', 'k_s', 'v_s')) if getattr(pool, n) is not None}
    cache = paged_lib.PagedKVCache(tables=jnp.asarray(tables),
                                   lengths=jnp.asarray(lengths), **planes)
    toks = jax.random.randint(key, (4, s), 0, cfg.vocab_size)
    path = paged_lib.decode_path(tables.shape, pool.k.shape, pool.k.dtype,
                                 quant) if s == 1 else 'gather'
    assert path == ('paged_kernel' if kernel else 'gather')
    _, new = paged_lib.forward_paged(params, toks, cache, cfg,
                                     jnp.asarray(active))
    np.testing.assert_array_equal(new.lengths, lengths + s)
    written = np.zeros((cfg.n_layers, nb, p), bool)  # [L, NB, P]
    for b in np.flatnonzero(active):
        for pos in range(lengths[b], lengths[b] + s):
            written[:, tables[b, pos // p], pos % p] = True
    assert written.sum() == cfg.n_layers * 3 * s
    for name, before in planes.items():
        before, after = np.asarray(before), np.asarray(getattr(new, name))
        same = np.moveaxis(before == after, 2, -1 if before.ndim == 4
                           else -2)  # heads last (of the row's numbers)
        same = same.reshape(written.shape + (-1,))
        assert not same[written].all(axis=-1).any(), name  # every row new
        junk = np.zeros_like(written)
        junk[0, 0] = True
        assert same[~written & ~junk].all(), name


def test_paged_kernel_leaves_int8_and_spec_on_the_gather(
        wide, kernel_traces, monkeypatch):
    """An int8 pool carries scales the kernel does not fold, and the
    speculative verify is S = k + 1: both keep the dense view, say so
    in stats(), and still produce their oracles' tokens."""
    from skypilot_tpu.ops import decode_attention
    cfg, params = wide
    monkeypatch.setattr(decode_attention, 'PAGED_INTERPRET', True)
    row = [7, 8, 9, 10]
    eng = _mk(params, cfg, slots=2, kv_quantize=True)
    try:
        assert eng.stats()['decode_attention'] == 'gather'
        assert eng.submit(row, 6).result(timeout=180) == _solo(
            params, cfg, row, 6, kv_quantize=True)
    finally:
        eng.stop()
    eng = _mk(params, cfg, slots=2, draft_params=params, draft_cfg=cfg,
              spec_k=2)
    try:
        assert eng.stats()['decode_attention'] == 'gather'
        assert eng.submit(row, 6).result(timeout=180) == _solo(
            params, cfg, row, 6)
    finally:
        eng.stop()
    assert not kernel_traces
    # and a float pool without a draft takes it (stated when the engine
    # builds its pool, before anything is traced)
    assert engine_lib.ContinuousEngine(
        params, cfg, slots=2,
        max_len=64).stats()['decode_attention'] == 'paged_kernel'
