"""Pipelined decode dispatch tests (models/engine.py).

The engine keeps ONE chunk in flight by default: chunk N+1 is
dispatched before chunk N's tokens are fetched, so host bookkeeping
(device_get, EOS truncation, callbacks, slot freeing, admission)
overlaps device compute. The contract pinned here: greedy output is
BYTE-IDENTICAL to the serial engine (and to the solo generate()
oracle) under every scheduling hazard pipelining introduces —
EOS-mid-chunk, slot reuse after EOS, and ``_drain_firsts`` racing an
in-flight chunk — at KV block lengths 16 and 8; and the new overlap
stats actually move.
"""
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu.models import engine as engine_lib
from skypilot_tpu.models import generate, llama

BLOCKS = (16, 8)  # KV block lengths


@pytest.fixture(scope='module')
def tiny():
    cfg = llama.TINY
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
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


def test_pipelined_default_greedy_matches_oracle_and_reports_overlap(
        tiny):
    """Default engine (pipeline on): > slots greedy requests force slot
    reuse behind an in-flight chunk; every stream must equal its solo
    generation, and the overlap counters must show the pipeline
    actually hid host work."""
    cfg, params = tiny
    eng = _mk(params, cfg)
    assert eng.pipeline_depth == 1  # on by default
    try:
        rows = [[5, 6, 7], [8, 9, 10, 11, 12], [13, 14],
                [15, 16, 17, 18], [19, 20, 21]]
        futs = [eng.submit(r, 6) for r in rows]
        for row, fut in zip(rows, futs):
            assert fut.result(timeout=120) == _solo(params, cfg, row, 6), \
                row
        pl = eng.stats()['pipeline']
        assert pl['pipeline_depth'] == 1
        assert pl['dispatches'] >= 2
        assert pl['host_overlap_ms'] > 0  # bookkeeping hid behind compute
        assert pl['dispatch_gap_ms'] > 0
    finally:
        eng.stop()


@pytest.mark.slow
@pytest.mark.parametrize('block', BLOCKS)
def test_pipelined_stream_byte_identical_to_serial(tiny, block):
    """The headline equivalence: the same greedy traffic through a
    pipelined and a serial engine yields byte-identical per-request
    token streams (both equal the oracle), at either block length."""
    cfg, params = tiny
    rows = [[5, 6, 7], [8, 9, 10, 11, 12], [13, 14],
            [15, 16, 17, 18], [19, 20, 21], [3, 4]]
    results = {}
    for pipe in (True, False):
        eng = _mk(params, cfg, chunk_steps=2, kv_block=block,
                  pipeline=pipe)
        assert eng.pipeline_depth == (1 if pipe else 0)
        try:
            futs = [eng.submit(r, 7) for r in rows]
            results[pipe] = [f.result(timeout=120) for f in futs]
        finally:
            eng.stop()
    assert results[True] == results[False]
    for row, got in zip(rows, results[True]):
        assert got == _solo(params, cfg, row, 7), row


@pytest.mark.slow
@pytest.mark.parametrize('block', BLOCKS)
def test_pipelined_eos_mid_chunk_and_slot_reuse(tiny, block):
    """EOS lands mid-chunk while the NEXT chunk is already in flight:
    the stream truncates at the stop id, the in-flight chunk's junk for
    the freed slot is dropped, and the slot is immediately reusable —
    the reuse insert overwrites the junk-advanced lengths."""
    cfg, params = tiny
    eng = _mk(params, cfg, slots=1, chunk_steps=2, kv_block=block)
    try:
        row = [5, 6, 7]
        solo = _solo(params, cfg, row, 10)
        eos = solo[3]  # known greedy 4th token: stops mid-chunk
        got = eng.submit(row, 10, eos=eos).result(timeout=120)
        assert got == solo[:4]
        # The retired in-flight chunk must not have appended junk.
        time.sleep(1.0)
        assert got == solo[:4]
        assert eng.stats()['active_slots'] == 0
        # Slot-reuse-after-EOS: the single slot decoded junk in flight;
        # the next request must still be exact.
        other = [40, 41, 42, 43, 44, 45]
        assert (eng.submit(other, 7).result(timeout=120)
                == _solo(params, cfg, other, 7))
    finally:
        eng.stop()


@pytest.mark.slow
@pytest.mark.parametrize('block', BLOCKS)
def test_pipelined_drain_firsts_race(tiny, block):
    """_drain_firsts resolving a first-token-eos request races the
    in-flight chunk (which was dispatched with that slot active): the
    delivered list must stay [first], and the slot must be reusable."""
    cfg, params = tiny
    eng = _mk(params, cfg, slots=1, kv_block=block)
    try:
        row = [5, 6, 7]
        first = _solo(params, cfg, row, 1)[0]
        got = eng.submit(row, 10, eos=first).result(timeout=120)
        assert got == [first]
        time.sleep(1.0)
        assert got == [first]
        other = [9, 8, 7]
        assert (eng.submit(other, 3).result(timeout=120)
                == _solo(params, cfg, other, 3))
    finally:
        eng.stop()


@pytest.mark.slow
def test_pipelined_streaming_callback_exact(tiny):
    """Retirement order under pipelining preserves the streaming
    contract: on_tokens chunks concatenate to exactly the final (solo)
    result — no dropped, duplicated, or post-completion tokens."""
    cfg, params = tiny
    eng = _mk(params, cfg, chunk_steps=2)
    try:
        chunks = []
        fut = eng.submit([5, 6, 7], 7, on_tokens=chunks.append)
        final = fut.result(timeout=120)
        assert final == _solo(params, cfg, [5, 6, 7], 7)
        time.sleep(0.5)  # let any stale in-flight retirement land
        assert [t for c in chunks for t in c] == final
    finally:
        eng.stop()


@pytest.mark.slow
def test_serial_engine_reports_bubble_not_overlap(tiny):
    """pipeline=False is the A/B control: depth 0, and the host time
    between fetch and redispatch surfaces as bubble_ms (the device
    idle the pipeline exists to close)."""
    cfg, params = tiny
    eng = _mk(params, cfg, pipeline=False)
    try:
        futs = [eng.submit([i + 2, i + 3], 6) for i in range(4)]
        for f in futs:
            f.result(timeout=120)
        pl = eng.stats()['pipeline']
        assert pl['pipeline_depth'] == 0
        assert pl['dispatches'] >= 2
        assert pl['bubble_ms'] > 0
    finally:
        eng.stop()


def test_moe_auto_serializes(tiny):
    """MoE expert capacity is per forward call: a stale in-flight
    active mask would change live rows' routing, so the engine must
    fall back to serial dispatch even when pipelining is requested."""
    cfg = dataclasses.replace(llama.MOE_TINY, expert_capacity_factor=4.0)
    params = llama.init_params(jax.random.PRNGKey(7), cfg)
    eng = engine_lib.ContinuousEngine(params, cfg, slots=2, max_len=32,
                                      pipeline=True)
    assert eng.pipeline_depth == 0


def test_spec_mode_auto_serializes(tiny):
    """Speculative rounds are host-synchronous (acceptance shapes the
    next round's inputs): nothing to keep in flight."""
    cfg, params = tiny
    eng = engine_lib.ContinuousEngine(params, cfg, slots=2, max_len=64,
                                      draft_params=params, draft_cfg=cfg,
                                      pipeline=True)
    assert eng.pipeline_depth == 0


@pytest.mark.slow
def test_idle_engine_wakes_immediately_on_submit(tiny, monkeypatch):
    """The idle loop parks in a LONG _wake.wait (no 50 ms poll burning
    a core); a submit must be admitted via the event, not the timeout.
    With the wait stretched to 30 s, a poll-reliant loop would blow the
    10 s result deadline."""
    cfg, params = tiny
    monkeypatch.setattr(engine_lib, '_IDLE_WAIT_S', 30.0)
    eng = _mk(params, cfg)
    try:
        warm = [1, 2, 3]
        assert (eng.submit(warm, 4).result(timeout=120)
                == _solo(params, cfg, warm, 4))
        time.sleep(0.5)  # engine is now parked in the 30 s idle wait
        row = [4, 5, 6]
        assert (eng.submit(row, 4).result(timeout=10)
                == _solo(params, cfg, row, 4))
    finally:
        eng.stop()
