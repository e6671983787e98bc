"""What an engine is when nothing is asked of it (models/engine.py).

One KV layout (the paged pool), one prefix cache (its block trie), and
one answer per flag: the defaults ``ContinuousEngine.__init__`` reads
are the ones ``env_flags.py`` declares. The keywords of the paths that
went (``kv_layout``, ``prefix_slots``) only check their input.
"""
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


def _solo(params, cfg, row, n, max_len=64):
    out = generate.generate(params, cfg, jnp.asarray([row], jnp.int32),
                            max_new_tokens=n, max_len=max_len)
    return np.asarray(out[0]).tolist()


@pytest.mark.parametrize('kw, names', [
    ({'kv_layout': 'slot'}, ('kv_layout', 'paged')),
    ({'prefix_slots': 2}, ('prefix_slots', 'prefix_share')),
])
def test_engine_refuses_the_removed_layout_and_pool_by_name(tiny, kw, names):
    """The two keywords select nothing any more: the only values taken
    are the ones that mean "what the engine does anyway", and anything
    else says what took its place."""
    cfg, params = tiny
    with pytest.raises(ValueError) as exc:
        engine_lib.ContinuousEngine(params, cfg, slots=2, max_len=32, **kw)
    assert all(n in str(exc.value) for n in names), exc.value
    # The values the benchmark harness still passes are accepted.
    engine_lib.ContinuousEngine(params, cfg, slots=2, max_len=32,
                                kv_layout='paged', prefix_slots=0)


# Flag -> what the engine made of it (as the registry would spell it).
_ENGINE_FLAGS = {
    'SKYTPU_LLM_ROLE': lambda e: e.role,
    'SKYTPU_LLM_SLOTS': lambda e: e.slots,
    'SKYTPU_LLM_CHUNK_STEPS': lambda e: e.chunk_steps,
    'SKYTPU_LLM_PREFILL_BATCH': lambda e: e.prefill_batch,
    'SKYTPU_LLM_KV_CACHE': lambda e: 'int8' if e.kv_quantize else 'bf16',
    'SKYTPU_LLM_KV_BLOCK': lambda e: e.kv_block,
    'SKYTPU_LLM_PIPELINE': lambda e: e.pipeline_depth,
    'SKYTPU_LLM_PREFILL_CHUNK': lambda e: e.prefill_chunk,
    'SKYTPU_LLM_PREFIX_SHARE': lambda e: int(e.prefix_share),
    'SKYTPU_LLM_SPEC_K': lambda e: e.spec_k,
    'SKYTPU_PREFIX_SUMMARY_MAX': lambda e: e._summary_max,
    'SKYTPU_KV_TIERS': lambda e: int(e._kv_tiers is not None),
}


@pytest.fixture(scope='module')
def default_engine(tiny):
    """An engine built from nothing but the flags' own defaults."""
    import os
    cfg, params = tiny
    saved = {k: os.environ.pop(k) for k in list(os.environ)
             if k.startswith('SKYTPU_')}
    try:
        eng = engine_lib.ContinuousEngine(params, cfg)
    finally:
        os.environ.update(saved)
    yield eng
    eng.stop()


def test_the_three_flags_of_the_removed_paths_are_not_declared():
    from skypilot_tpu import env_flags
    names = {f.name.removeprefix('SKYTPU_') for f in env_flags.FLAGS}
    assert not names & {'LLM_KV_LAYOUT', 'LLM_PREFIX_CACHE', 'DECODE_KERNEL'}
    assert len(env_flags.FLAGS) == 146


@pytest.mark.parametrize('flag', sorted(_ENGINE_FLAGS))
def test_engine_default_is_the_registrys_default(default_engine, flag):
    """One answer per flag: with the environment unset, what
    ``ContinuousEngine.__init__`` reads each flag as is the default
    ``env_flags.py`` (and so ``docs/env_flags.md``) states."""
    from skypilot_tpu import env_flags
    assert str(_ENGINE_FLAGS[flag](default_engine)) == \
        env_flags.get(flag).default, flag


def _assert_one_layout_one_trie(stats):
    assert stats['kv_layout'] == 'paged'
    assert stats['decode_attention'] in ('paged_kernel', 'gather')
    assert stats['prefix_share']['enabled'] is True
    kb = stats['kv_blocks']
    assert kb['free'] + kb['cached'] == kb['usable'] > 0
    assert 'prefix_cache' not in stats


def test_engine_without_keywords_is_paged_with_a_live_trie(tiny):
    cfg, params = tiny
    eng = engine_lib.ContinuousEngine(params, cfg)
    _assert_one_layout_one_trie(eng.stats())
    eng.start()
    try:
        row = list(range(1, 40))
        want = _solo(params, cfg, row, 4, max_len=128)
        assert eng.submit(row, 4).result(timeout=120) == want
        assert eng.submit(row, 4).result(timeout=120) == want
        st = eng.stats()
        assert st['prefix_share']['hits'] == 1
        assert st['prefill_tokens_saved'] == 32      # two blocks of 16
    finally:
        eng.stop()


def test_llm_server_without_flags_is_paged_with_a_live_trie(monkeypatch):
    from skypilot_tpu.serve import llm_server as llm_mod
    for name in ('SKYTPU_LLM_PREFIX_SHARE', 'SKYTPU_LLM_ENGINE',
                 'SKYTPU_LLM_DRAFT', 'SKYTPU_LLM_KV_BLOCKS'):
        monkeypatch.delenv(name, raising=False)
    server = llm_mod.LlmServer('tiny', max_len=64)
    try:
        _assert_one_layout_one_trie(server.engine.stats())
        args = llm_mod.build_parser().parse_args(['--model', 'tiny'])
        assert not hasattr(args, 'kv_layout')
        assert not hasattr(args, 'prefix_cache')
    finally:
        server.engine.stop()
