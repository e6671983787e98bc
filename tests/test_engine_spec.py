"""Speculative decoding INSIDE the continuous engine (r4 verdict Next
#2): per-slot draft-propose/target-verify rounds.

The contract is the engine's own, unchanged: every greedy request's
output is EXACTLY its solo greedy generation (generate() is the oracle)
no matter when it was admitted, which slot it landed in, what junk the
freed slots decode, or what the draft model proposes — the draft only
changes SPEED. Sampled requests advance one verified token per round
(drawn from the verify's position-0 logits = the plain decode step's
logits) and keep their distributional semantics.
"""
import dataclasses

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
def draft():
    """A draft over the same vocab but DIFFERENT weights: proposals
    frequently diverge from the target, exercising rejection/rollback.
    (Same-params drafts exercise the full-acceptance path separately.)"""
    cfg = llama.TINY
    params = llama.init_params(jax.random.PRNGKey(99), cfg)
    return cfg, params


def _solo(params, cfg, row, n, max_len=64):
    out = generate.generate(params, cfg, jnp.asarray([row], jnp.int32),
                            max_new_tokens=n, max_len=max_len)
    return np.asarray(out[0]).tolist()


def _mk(params, cfg, d_params, d_cfg, **kw):
    kw.setdefault('slots', 4)
    kw.setdefault('max_len', 64)
    kw.setdefault('spec_k', 3)
    eng = engine_lib.ContinuousEngine(params, cfg, draft_params=d_params,
                                      draft_cfg=d_cfg, **kw)
    eng.start()
    return eng


def test_spec_greedy_matches_generate_with_divergent_draft(tiny, draft):
    cfg, params = tiny
    d_cfg, d_params = draft
    eng = _mk(params, cfg, d_params, d_cfg)
    try:
        rows = [[5, 6, 7], [8, 9, 10, 11, 12], [13, 14],
                [15, 16, 17, 18], [19, 20, 21]]  # > slots: forces reuse
        futs = [eng.submit(r, 6) for r in rows]
        for row, fut in zip(rows, futs):
            assert fut.result(timeout=120) == _solo(params, cfg, row, 6), \
                row
        st = eng.stats()['speculative']
        assert st is not None and st['rounds'] >= 1
        assert st['proposals'] > 0
    finally:
        eng.stop()


def test_spec_identical_draft_reaches_full_acceptance(tiny):
    """With draft == target every greedy proposal is the target's own
    argmax: acceptance must be 100% and each round commits k+1 tokens."""
    cfg, params = tiny
    eng = _mk(params, cfg, params, cfg, spec_k=3)
    try:
        row = [5, 6, 7, 8]
        got = eng.submit(row, 9).result(timeout=120)
        assert got == _solo(params, cfg, row, 9)
        st = eng.stats()['speculative']
        assert st['acceptance_rate'] == 1.0
        # 1 prefill token + 8 engine tokens at k+1=4/round -> 2 rounds.
        assert st['rounds'] <= 3
    finally:
        eng.stop()


def test_spec_mid_stream_admission_stays_exact(tiny, draft):
    import time
    cfg, params = tiny
    d_cfg, d_params = draft
    eng = _mk(params, cfg, d_params, d_cfg)
    try:
        long_row = [3, 4, 5, 6]
        f1 = eng.submit(long_row, 20)
        deadline = time.time() + 60
        while eng.spec_rounds < 1 and time.time() < deadline:
            time.sleep(0.01)
        assert eng.spec_rounds >= 1, 'engine never started spec rounds'
        late_row = [9, 8, 7]
        f2 = eng.submit(late_row, 4)
        assert f2.result(timeout=120) == _solo(params, cfg, late_row, 4)
        assert f1.result(timeout=120) == _solo(params, cfg, long_row, 20)
    finally:
        eng.stop()


def test_spec_slot_reuse_resets_both_caches(tiny, draft):
    cfg, params = tiny
    d_cfg, d_params = draft
    eng = _mk(params, cfg, d_params, d_cfg, slots=1)
    try:
        a = eng.submit([1, 2, 3], 5)
        assert a.result(timeout=120) == _solo(params, cfg, [1, 2, 3], 5)
        b = eng.submit([40, 41, 42, 43, 44, 45], 7)
        assert b.result(timeout=120) == _solo(
            params, cfg, [40, 41, 42, 43, 44, 45], 7)
    finally:
        eng.stop()


def test_spec_with_kv_int8_matches_kv_int8_oracle(tiny, draft):
    """int8 KV quantization is per position and deterministic, so spec
    rollback replays exactly the codes sequential decode writes."""
    cfg, params = tiny
    d_cfg, d_params = draft
    eng = _mk(params, cfg, d_params, d_cfg, kv_quantize=True)
    try:
        row = [7, 8, 9, 10]
        want = np.asarray(generate.generate(
            params, cfg, jnp.asarray([row], jnp.int32), max_new_tokens=6,
            max_len=64, kv_quantize=True)[0]).tolist()
        assert eng.submit(row, 6).result(timeout=120) == want
    finally:
        eng.stop()


def test_spec_sampled_rows_advance_one_token_per_round(tiny, draft):
    """A sampled request shares the spec engine: valid output of the
    right length, while a concurrent greedy request stays exact."""
    cfg, params = tiny
    d_cfg, d_params = draft
    eng = _mk(params, cfg, d_params, d_cfg)
    try:
        g = eng.submit([5, 6, 7], 6)
        s = eng.submit([8, 9, 10], 6, temperature=1.0, top_k=8)
        assert g.result(timeout=120) == _solo(params, cfg, [5, 6, 7], 6)
        out = s.result(timeout=120)
        assert len(out) == 6
        assert all(0 <= t < cfg.vocab_size for t in out)
    finally:
        eng.stop()


def test_spec_eos_mid_window_stops_and_frees(tiny):
    """An eos landing INSIDE an accepted window truncates the emission
    at the stop id and frees the slot (identical draft guarantees the
    window actually contains multiple accepted tokens)."""
    cfg, params = tiny
    eng = _mk(params, cfg, params, cfg, spec_k=3)
    try:
        row = [5, 6, 7]
        solo = _solo(params, cfg, row, 10)
        eos = solo[3]  # known greedy 4th token — mid-window at k=3
        got = eng.submit(row, 10, eos=eos).result(timeout=120)
        assert got == solo[:4]
        assert eng.stats()['active_slots'] == 0
        got2 = eng.submit(row, 4, eos=[99999]).result(timeout=120)
        assert got2 == solo[:4]
    finally:
        eng.stop()


def test_spec_streaming_callback_sees_exact_stream(tiny, draft):
    cfg, params = tiny
    d_cfg, d_params = draft
    eng = _mk(params, cfg, d_params, d_cfg)
    try:
        seen = []
        row = [11, 12, 13]
        fut = eng.submit(row, 8, on_tokens=lambda t: seen.append(list(t)))
        want = _solo(params, cfg, row, 8)
        assert fut.result(timeout=120) == want
        assert [t for chunk in seen for t in chunk] == want
    finally:
        eng.stop()


def test_spec_chunked_prefill_exact(tiny, draft):
    """Long prompts chunk into BOTH caches (the draft lags the target's
    prefix-free start by nothing here) and the output stays exact."""
    cfg, params = tiny
    d_cfg, d_params = draft
    eng = _mk(params, cfg, d_params, d_cfg, prefill_chunk=8)
    try:
        long_row = list(range(1, 31))  # 30 tokens -> 4 chunks each model
        got = eng.submit(long_row, 6).result(timeout=120)
        assert got == _solo(params, cfg, long_row, 6)
        st = eng.stats()
        assert st['prefill_chunks'] >= 8  # target + draft chunks
        assert st['prefilling'] == 0 and st['active_slots'] == 0
        short = [5, 6, 7]
        assert eng.submit(short, 4).result(timeout=120) == \
            _solo(params, cfg, short, 4)
    finally:
        eng.stop()


def test_spec_tensor_parallel_matches_single_device(tiny, draft):
    """Spec rounds compile SPMD under a TP mesh (draft shards by the
    same logical rules) and outputs still match solo generation."""
    from skypilot_tpu.parallel import mesh as mesh_lib
    cfg, params = tiny
    d_cfg, d_params = draft
    mesh = mesh_lib.build_mesh(mesh_lib.MeshSpec(fsdp=1, tensor=2),
                               devices=jax.devices()[:2])
    eng = _mk(params, cfg, d_params, d_cfg, mesh=mesh)
    try:
        rows = [[5, 6, 7], [8, 9, 10, 11]]
        futs = [eng.submit(r, 6) for r in rows]
        for row, fut in zip(rows, futs):
            assert fut.result(timeout=180) == _solo(params, cfg, row, 6)
    finally:
        eng.stop()


def test_spec_with_paged_kv_identical_draft(tiny):
    """Spec x paged: the verify is a multi-token paged forward (writes
    span blocks: blocks of 8 here, so a k+1 = 4 window crosses one),
    rollback is the same lengths rewind, and block reservations carry
    the k+1 window overhang. Identical draft => 100% acceptance,
    byte-exact."""
    cfg, params = tiny
    eng = _mk(params, cfg, params, cfg, spec_k=3, kv_block=8)
    try:
        row = [5, 6, 7, 8]
        got = eng.submit(row, 9).result(timeout=120)
        assert got == _solo(params, cfg, row, 9)
        st = eng.stats()
        assert st['speculative']['acceptance_rate'] == 1.0
        assert st['kv_layout'] == 'paged'
        assert st['kv_blocks']['free'] == st['kv_blocks']['total'] - 1
    finally:
        eng.stop()


def test_spec_with_paged_kv_divergent_draft_and_reuse(tiny, draft):
    cfg, params = tiny
    d_cfg, d_params = draft
    # A pool of two usable blocks for two slots (full capacity is 8):
    # each row reserves one (prompt + 6 + the k+1 overhang <= 16).
    eng = _mk(params, cfg, d_params, d_cfg, slots=2, kv_blocks=3)
    try:
        rows = [[5, 6, 7], [8, 9, 10, 11], [12, 13, 14]]  # reuse
        futs = [eng.submit(r, 6) for r in rows]
        for row, fut in zip(rows, futs):
            assert fut.result(timeout=120) == _solo(params, cfg, row, 6)
    finally:
        eng.stop()


def test_spec_with_paged_kv_int8_and_eos(tiny):
    cfg, params = tiny
    eng = _mk(params, cfg, params, cfg, spec_k=3, kv_quantize=True)
    try:
        row = [5, 6, 7]
        want = np.asarray(generate.generate(
            params, cfg, jnp.asarray([row], jnp.int32),
            max_new_tokens=10, max_len=64, kv_quantize=True)[0]).tolist()
        eos = want[3]
        got = eng.submit(row, 10, eos=eos).result(timeout=120)
        assert got == want[:4]
        assert eng.stats()['active_slots'] == 0
    finally:
        eng.stop()


def test_spec_rejects_moe_target(tiny):
    moe_cfg = dataclasses.replace(llama.MOE_TINY,
                                  expert_capacity_factor=4.0)
    moe_params = llama.init_params(jax.random.PRNGKey(7), moe_cfg)
    cfg, params = tiny
    with pytest.raises(ValueError, match='dense target'):
        engine_lib.ContinuousEngine(
            moe_params, moe_cfg, draft_params=params, draft_cfg=cfg)


def test_spec_submit_cap_reserves_window_overhang(tiny, draft):
    cfg, params = tiny
    d_cfg, d_params = draft
    eng = _mk(params, cfg, d_params, d_cfg, max_len=32, spec_k=3)
    try:
        with pytest.raises(ValueError, match='verify window overhang'):
            eng.submit(list(range(20)), 9)  # 29 > 32 - 4
        f = eng.submit(list(range(20)), 8)  # 28 == the limit
        assert f.result(timeout=120) == _solo(params, cfg,
                                              list(range(20)), 8,
                                              max_len=32)
    finally:
        eng.stop()


def test_generate_speculative_rejects_moe_target():
    from skypilot_tpu.models import speculative
    moe_cfg = dataclasses.replace(llama.MOE_TINY,
                                  expert_capacity_factor=4.0)
    moe_params = llama.init_params(jax.random.PRNGKey(7), moe_cfg)
    d_params = llama.init_params(jax.random.PRNGKey(1), llama.TINY)
    with pytest.raises(ValueError, match='dense target'):
        speculative.generate_speculative(
            moe_params, moe_cfg, d_params, llama.TINY,
            jnp.asarray([[1, 2, 3]], jnp.int32), 4)


def test_llm_server_engine_with_draft_roundtrip(tiny):
    """--draft-model composes with --engine continuous end-to-end: the
    HTTP path serves byte-exact greedy output and /health exposes the
    engine's speculative counters."""
    import threading

    import requests as requests_lib
    from aiohttp import web

    from skypilot_tpu.serve import llm_server as llm_mod
    from skypilot_tpu.utils import common_utils

    cfg, params = tiny
    server = llm_mod.LlmServer('tiny', max_len=64, engine='continuous',
                               draft_model='tiny')
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
    row = [5, 6, 7, 8]
    r = requests_lib.post(
        f'http://127.0.0.1:{port}/generate',
        json={'tokens': [row], 'max_new_tokens': 6}, timeout=180)
    assert r.status_code == 200
    assert r.json()['tokens'][0] == _solo(params, cfg, row, 6)
    h = requests_lib.get(f'http://127.0.0.1:{port}/health', timeout=30)
    spec = h.json()['engine']['speculative']
    assert spec['rounds'] >= 1
    server.engine.stop()


def test_llm_server_rejects_moe_target_with_draft():
    from skypilot_tpu.serve import llm_server as llm_mod
    with pytest.raises(ValueError, match='dense target'):
        llm_mod.LlmServer('moe-tiny', max_len=64, engine='off',
                          draft_model='tiny')
