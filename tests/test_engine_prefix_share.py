"""Copy-on-write block-level prefix sharing in the paged KV engine.

Contract (ISSUE 6 tentpole): committed full prompt blocks are indexed
in a refcounted trie (models/paged.py BlockTrie); a matching request's
block table points at the shared blocks — a hit is a table write, not a
KV copy — and only the unshared tail prefills. Greedy output must be
byte-identical sharing ON vs OFF (and to the solo oracle) across paged
x chunked-prefill x int8; a partially matched tail block forks
copy-on-write; release paths decref instead of freeing; and after a
full drain the free/owned/shared/cached block states reconcile exactly
(no leaked blocks).
"""
import time

import jax
import numpy as np
import pytest

from skypilot_tpu.models import engine as engine_lib
from skypilot_tpu.models import generate, llama
from skypilot_tpu.models import paged as paged_lib


@pytest.fixture(scope='module')
def tiny():
    cfg = llama.TINY
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _solo(params, cfg, row, n, max_len=64, **kw):
    out = generate.generate(params, cfg, np.asarray([row], np.int32),
                            max_new_tokens=n, max_len=max_len, **kw)
    return np.asarray(out[0]).tolist()


def _mk(params, cfg, **kw):
    kw.setdefault('slots', 4)
    kw.setdefault('max_len', 64)
    kw.setdefault('chunk_steps', 2)
    eng = engine_lib.ContinuousEngine(params, cfg, **kw)
    eng.start()
    return eng


HEAD = [((11 * j) % 250) + 1 for j in range(24)]  # 1 full block + 8


def _mixed_rows(n=12, shared_frac=0.75, tail=8):
    rows = []
    for i in range(n):
        if (i * shared_frac) % 1 < shared_frac:
            rows.append(HEAD + [((7 * i + j) % 250) + 1
                                for j in range(tail)])
        else:
            rows.append([((13 * i + j) % 250) + 1
                         for j in range(len(HEAD) + tail)])
    return rows


def _drained(eng):
    """Block states after a full drain: nothing owned or referenced,
    free + cached == usable — and the hierarchical-tier counts (host /
    spilled, OFF-device by contract) must reconcile exactly with the
    kv_tiers stats block, never leak into the device partition."""
    st = eng.stats()
    kb = st['kv_blocks']
    tiers = st.get('kv_tiers') or {}
    assert kb['host'] == (tiers.get('host_blocks') or 0), st
    assert kb['spilled'] == (tiers.get('spilled_blocks') or 0), st
    return (kb['owned'] == 0 and kb['shared'] == 0
            and kb['free'] + kb['cached'] == kb['usable'])


def test_share_greedy_byte_parity_on_vs_off(tiny):
    cfg, params = tiny
    rows = _mixed_rows()
    outs = {}
    stats = {}
    for share in (True, False):
        eng = _mk(params, cfg, prefix_share=share)
        try:
            # Seed sequentially so the head's blocks are committed
            # before the sharers arrive (concurrent first sightings all
            # miss, like any cache).
            f0 = eng.submit(rows[0], 6)
            out = [f0.result(timeout=300)]
            futs = [eng.submit(r, 6) for r in rows[1:]]
            out += [f.result(timeout=300) for f in futs]
            outs[share] = out
            stats[share] = eng.stats()
        finally:
            eng.stop()
    assert outs[True] == outs[False]
    for row, got in zip(rows, outs[True]):
        assert got == _solo(params, cfg, row, 6), row
    st = stats[True]['prefix_share']
    assert st['enabled'] and st['hits'] >= 1, st
    assert st['hit_tokens'] >= 16, st
    assert st['cow_forks'] >= 1, st  # 24-token head: full block + 8
    assert stats[True]['prefill_tokens'] < stats[False]['prefill_tokens']
    assert not stats[False]['prefix_share']['enabled']
    # every hit's tail (8 tokens padded to 16) by the one rule
    path = paged_lib.write_path(16, 16, cfg.n_kv_heads)
    assert stats[True]['pool_write'][path] == st['hits'], stats[True]
    assert sum(stats[True]['pool_write'].values()) == st['hits']
    assert stats[False]['pool_write'] == {'blocks': 0, 'rows': 0}


@pytest.mark.parametrize('tail,path', [(8, 'rows'), (20, 'blocks')])
def test_pool_write_counts_each_shared_prefill_by_its_path(tiny, tail,
                                                           path):
    """A tail of 8 tokens pads to 16 positions, 32 rows of the tiny
    model's 2 heads: by rows (paged.write_path); one of 20 pads to 32,
    64 rows, four blocks' positions: by blocks. Either way the stream
    is the solo oracle's."""
    cfg, params = tiny
    eng = _mk(params, cfg, max_len=128)
    try:
        head = [((17 * j) % 250) + 1 for j in range(32)]
        a = head + [3, 4, 5]
        b = head + [((5 * j) % 250) + 1 for j in range(tail)]
        for row in (a, b):
            assert eng.submit(row, 4).result(timeout=300) == \
                _solo(params, cfg, row, 4, max_len=128)
        st = eng.stats()
        assert st['prefix_share']['hits'] == 1, st['prefix_share']
        width = engine_lib.prompt_bucket(tail)
        assert paged_lib.write_path(width, 16, cfg.n_kv_heads) == path
        assert st['pool_write'] == {'blocks': int(path == 'blocks'),
                                    'rows': int(path == 'rows')}
    finally:
        eng.stop()


def test_pool_write_under_tensor_parallel_counts_a_shards_heads(tiny):
    """Under TP each shard writes its own kv heads (``_cache_step``
    under ``shard_map``): the tiny model's 2 heads over a tensor axis of
    2 leave one a shard, so a tail padded to 32 is 32 rows there, under
    four blocks' positions: by rows, and the count says so."""
    from skypilot_tpu.parallel import mesh as mesh_lib
    cfg, params = tiny
    mesh = mesh_lib.build_mesh(mesh_lib.MeshSpec(fsdp=1, tensor=2),
                               devices=jax.devices()[:2])
    eng = _mk(params, cfg, max_len=128, mesh=mesh)
    try:
        head = [((17 * j) % 250) + 1 for j in range(32)]
        for row in (head + [3, 4, 5],
                    head + [((3 * j) % 250) + 1 for j in range(20)]):
            assert eng.submit(row, 4).result(timeout=300) == \
                _solo(params, cfg, row, 4, max_len=128)
        assert paged_lib.write_path(32, 16, cfg.n_kv_heads // 2) == 'rows'
        assert eng.stats()['pool_write'] == {'blocks': 0, 'rows': 1}
    finally:
        eng.stop()


def test_share_cow_fork_on_divergent_append(tiny):
    """Two prompts share 24 tokens (1 full block + 8 into the next):
    the second request's partial match must FORK the donor block, and
    both streams stay byte-exact — the fork must never scribble on the
    donor's live KV."""
    cfg, params = tiny
    eng = _mk(params, cfg)
    try:
        a = HEAD + [31, 32, 33, 34, 35, 36, 37, 38]  # 32: 2 full blocks
        b = HEAD + [41, 42, 43, 44, 45, 46, 47, 48]  # diverges in blk 2
        fa = eng.submit(a, 8)
        assert fa.result(timeout=300) == _solo(params, cfg, a, 8)
        fb = eng.submit(b, 8)
        fa2 = eng.submit(a, 8)  # donor's chain must still be intact
        assert fb.result(timeout=300) == _solo(params, cfg, b, 8)
        assert fa2.result(timeout=300) == _solo(params, cfg, a, 8)
        st = eng.stats()
        assert st['prefix_share']['cow_forks'] >= 1, st
        assert st['prefix_share']['hits'] >= 2, st
    finally:
        eng.stop()


def test_share_chunked_prefill_tail_only(tiny):
    """Long prompts compose: the chunked path seeds its scratch from
    the trie and computes only the unshared tail."""
    cfg, params = tiny
    long_row = HEAD + list(range(100, 130))  # 54 tokens
    outs = {}
    for share in (True, False):
        eng = _mk(params, cfg, prefill_chunk=8, prefix_share=share)
        try:
            seed = eng.submit(HEAD + list(range(150, 170)), 4)
            out = [seed.result(timeout=300)]
            t0 = eng.prefill_tokens
            f = eng.submit(long_row, 4)
            out.append(f.result(timeout=300))
            outs[share] = (out, eng.prefill_tokens - t0)
        finally:
            eng.stop()
    assert outs[True][0] == outs[False][0]
    assert outs[True][0][1] == _solo(params, cfg, long_row, 4)
    # The shared run prefilled only the tail of the long prompt.
    assert outs[True][1] <= outs[False][1] - 16, outs


def test_share_int8_kv_parity(tiny):
    cfg, params = tiny
    rows = [HEAD + [61, 62, 63], HEAD + [71, 72]]
    eng = _mk(params, cfg, kv_quantize=True)
    try:
        f0 = eng.submit(rows[0], 6)
        want0 = _solo(params, cfg, rows[0], 6, kv_quantize=True)
        assert f0.result(timeout=300) == want0
        f1 = eng.submit(rows[1], 6)
        assert f1.result(timeout=300) == _solo(params, cfg, rows[1], 6,
                                               kv_quantize=True)
        assert eng.stats()['prefix_share']['hits'] >= 1
    finally:
        eng.stop()


def test_share_eos_and_drain_reconcile_exactly(tiny):
    """EOS frees early via DECREF; after a full drain free + cached ==
    usable with nothing owned or referenced (no leaked blocks)."""
    cfg, params = tiny
    eng = _mk(params, cfg)
    try:
        row = HEAD + [91, 92, 93]
        solo = _solo(params, cfg, row, 10)
        eng.submit(row, 10).result(timeout=300)
        eos = solo[3]
        got = eng.submit(row, 10, eos=eos).result(timeout=300)
        assert got == solo[:4]
        deadline = time.time() + 30
        while not _drained(eng):
            assert time.time() < deadline, eng.stats()['kv_blocks']
            time.sleep(0.05)
        kb = eng.stats()['kv_blocks']
        assert kb['cached'] >= 1  # the committed head stayed cached
    finally:
        eng.stop()


def test_share_eviction_under_pool_pressure(tiny):
    """A pool too small to hold cached prefixes AND new admissions must
    evict idle blocks (refcount-aware LRU) instead of deadlocking, and
    stay byte-exact; referenced blocks are never evicted."""
    cfg, params = tiny
    # 4 usable blocks; each 28-token prompt + 6 new needs 3 and leaves
    # 1 cached block behind — the third admission must evict.
    eng = _mk(params, cfg, kv_blocks=5)
    try:
        heads = [[((17 * h + j) % 250) + 1 for j in range(24)]
                 for h in range(3)]
        for h in heads:
            row = h + [5, 6, 7, 8]
            assert eng.submit(row, 6).result(timeout=300) == \
                _solo(params, cfg, row, 6)
        st = eng.stats()
        assert st['prefix_share']['evictions'] >= 1, st
        # Repeat of the NEWEST head should still hit (LRU kept it).
        row = heads[-1] + [9, 9, 9]
        hits0 = eng.stats()['prefix_share']['hits']
        assert eng.submit(row, 6).result(timeout=300) == \
            _solo(params, cfg, row, 6)
        assert eng.stats()['prefix_share']['hits'] == hits0 + 1
        assert _drained(eng) or eng.stats()['kv_blocks']['owned'] == 0
    finally:
        eng.stop()


def test_share_backpressure_with_referenced_blocks(tiny):
    """Referenced (shared) blocks must not be evicted: a holder keeps
    the shared head pinned while the pool backpressures younger
    requests — all complete, none corrupt."""
    cfg, params = tiny
    eng = _mk(params, cfg, kv_blocks=6)  # 5 usable
    try:
        base = HEAD + [3, 4]
        holder = eng.submit(base, 20)  # 26+20 = 46 -> 3 blocks, long-lived
        others = [eng.submit([((23 * i + j) % 250) + 1
                              for j in range(10)], 8)
                  for i in range(3)]  # 2 blocks each: must serialize
        assert holder.result(timeout=300) == _solo(params, cfg, base, 20)
        for i, f in enumerate(others):
            row = [((23 * i + j) % 250) + 1 for j in range(10)]
            assert f.result(timeout=300) == _solo(params, cfg, row, 8)
        deadline = time.time() + 30
        while not _drained(eng):
            assert time.time() < deadline, eng.stats()['kv_blocks']
            time.sleep(0.05)
    finally:
        eng.stop()


def test_share_hit_near_full_context_no_clip_corruption(tiny):
    """A hit whose shared head + power-of-two-padded tail would
    overhang max_len must clamp the pad width: clipped writes land in
    the request's OWN last reserved block (a full-table reservation has
    no junk-sink entry to absorb them) and would scribble over real
    prompt KV. 80 shared + 40 unique tokens at max_len 128 pads the
    40-token tail to 64 unclamped — 16 positions past the table."""
    cfg, params = tiny
    eng = _mk(params, cfg, max_len=128)
    try:
        head = [((29 * j) % 250) + 1 for j in range(80)]
        a = head + [((3 * j) % 250) + 1 for j in range(2)]  # commits 5
        assert eng.submit(a, 6).result(timeout=300) == \
            _solo(params, cfg, a, 6, max_len=128)
        b = head + [((5 * j) % 250) + 1 for j in range(40)]  # 120 toks
        got = eng.submit(b, 8).result(timeout=300)
        assert got == _solo(params, cfg, b, 8, max_len=128)
        assert eng.stats()['prefix_share']['hits'] >= 1
    finally:
        eng.stop()


def test_share_hit_parks_when_matched_chain_is_the_idle_supply(tiny):
    """Admission must not count the matched chain's own idle blocks as
    allocatable supply: it pins them before allocating, and with the
    free list empty the allocator would pop nothing and crash the
    engine thread. Pool of 3: A caches 2 idle blocks, C holds the one
    free block, then B's hit (2 pinned + 1 owned needed) must PARK
    until C completes — and still come out byte-exact."""
    cfg, params = tiny
    eng = _mk(params, cfg, kv_blocks=4)  # 3 usable
    try:
        a = [((31 * j) % 250) + 1 for j in range(32)]
        assert eng.submit(a, 2).result(timeout=300) == \
            _solo(params, cfg, a, 2)
        c_row = [9, 8, 7]
        c = eng.submit(c_row, 12)       # occupies the 1 free block
        b_row = a + [5, 6, 7, 8]
        b = eng.submit(b_row, 8)        # hit on A's 2 cached blocks
        assert c.result(timeout=300) == _solo(params, cfg, c_row, 12)
        assert b.result(timeout=300) == _solo(params, cfg, b_row, 8)
        assert eng.stats()['prefix_share']['hits'] >= 1
        deadline = time.time() + 30
        while not _drained(eng):
            assert time.time() < deadline, eng.stats()['kv_blocks']
            time.sleep(0.05)
    finally:
        eng.stop()


def test_share_disabled_for_moe_and_spec(tiny):
    cfg, params = tiny
    moe = engine_lib.ContinuousEngine(
        llama.init_params(jax.random.PRNGKey(1), llama.MOE_TINY),
        llama.MOE_TINY, slots=2, max_len=32)
    assert not moe.prefix_share
    spec = engine_lib.ContinuousEngine(
        params, cfg, slots=2, max_len=64,
        draft_params=params, draft_cfg=cfg)
    assert not spec.prefix_share
    # A dense target without a draft shares with no keyword at all.
    plain = engine_lib.ContinuousEngine(params, cfg, slots=2, max_len=64)
    assert plain.prefix_share and plain._trie is not None


def test_stats_surface_share_counters(tiny):
    cfg, params = tiny
    eng = _mk(params, cfg)
    try:
        st = eng.stats()
        kb = st['kv_blocks']
        for key in ('free', 'usable', 'used', 'owned', 'shared',
                    'cached', 'host', 'spilled'):
            assert key in kb, kb
        ps = st['prefix_share']
        for key in ('enabled', 'hits', 'misses', 'hit_rate',
                    'hit_tokens', 'commits', 'evictions', 'cow_forks'):
            assert key in ps, ps
        assert st['pool_write'] == {'blocks': 0, 'rows': 0}
        assert 'prefill_tokens' in st and 'prefill_tokens_saved' in st
        assert 'prefill_bubble_ms' in st
    finally:
        eng.stop()


# ---------------------------------------------------------------------------
# BlockTrie unit tests (pure host logic).


def test_trie_match_commit_refcounts():
    t = paged_lib.BlockTrie(4)
    row = list(range(1, 14))  # 13 tokens -> 3 full blocks of 4
    assert t.match(row) == ([], None, 0)
    n1 = t.commit(None, tuple(row[0:4]), 10)
    n2 = t.commit(n1, tuple(row[4:8]), 11)
    nodes, partial, plen = t.match(row)
    assert [n.block for n in nodes] == [10, 11]
    assert partial is None and plen == 0
    # match is capped at len(row) - 1: an exactly-covered prompt must
    # leave its last token to compute.
    nodes, _, _ = t.match(row[:9])  # limit 8 -> both blocks
    assert len(nodes) == 2
    nodes, _, _ = t.match(row[:8])  # limit 7 -> only block 1
    assert [n.block for n in nodes] == [10]
    # Refcounts: commit holds one ref; release parks in the idle LRU.
    assert t.referenced == 2 and t.reclaimable == 0
    assert t.release(n1) is None and t.release(n2) is None
    assert t.referenced == 0 and t.reclaimable == 2
    t.acquire(n1)
    assert t.referenced == 1 and t.reclaimable == 1


def test_trie_partial_match_names_fork_donor():
    t = paged_lib.BlockTrie(4)
    committed = [1, 2, 3, 4, 5, 6, 7, 8]
    n1 = t.commit(None, tuple(committed[:4]), 10)
    t.commit(n1, tuple(committed[4:]), 11)
    row = [1, 2, 3, 4, 5, 6, 99, 98, 97]  # diverges 2 tokens into blk 2
    nodes, partial, plen = t.match(row)
    assert [n.block for n in nodes] == [10]
    assert partial is not None and partial.block == 11 and plen == 2


def test_trie_eviction_cascades_and_detaches():
    t = paged_lib.BlockTrie(2)
    a = t.commit(None, (1, 2), 10)
    b = t.commit(a, (3, 4), 11)
    c = t.commit(b, (5, 6), 12)
    t.release(a)
    t.release(c)  # b stays referenced
    assert t.reclaimable == 2
    freed = t.evict(1)  # pops a (LRU) -> cascades idle c, detaches b
    assert sorted(freed) == [10, 12]
    assert b.detached and t.match([1, 2, 3, 4, 5]) == ([], None, 0)
    # The detached survivor frees directly at its last release.
    assert t.release(b) == 11
    assert t.referenced == 0 and t.reclaimable == 0


def test_loadgen_shared_prefix_heads_deterministic():
    """--shared-prefix heads are deterministic per tenant (the same
    tenant always repeats the same head — the whole point) and
    distinct across tenants."""
    from skypilot_tpu.serve import loadgen
    p0 = loadgen.shared_prefix_tokens(0, 24, 256)
    assert p0 == loadgen.shared_prefix_tokens(0, 24, 256)
    assert p0 != loadgen.shared_prefix_tokens(1, 24, 256)
    assert len(p0) == 24 and all(1 <= t < 256 for t in p0)


def test_engine_prefix_summary_advertises_resident_chains(tiny,
                                                          monkeypatch):
    """The engine's /health advert (ISSUE 12): after shared-head
    traffic, prefix_summary() exposes chains an LB-side hash of the
    same prompt matches; SKYTPU_PREFIX_SUMMARY_MAX is a hard entry
    bound; a share-off engine adverts nothing."""
    from skypilot_tpu.utils import prefix_affinity
    cfg, params = tiny
    monkeypatch.setenv('SKYTPU_PREFIX_SUMMARY_MAX', '2')
    eng = _mk(params, cfg)
    try:
        a = HEAD + [31, 32, 33, 34, 35, 36, 37, 38]
        eng.submit(a, 6).result(timeout=300)
        eng.submit(HEAD + [41, 42, 43, 44, 45, 46, 47, 48],
                   6).result(timeout=300)
        summary = eng.prefix_summary()
        assert summary is not None and summary['entries'], summary
        assert len(summary['entries']) <= 2  # the env bound, enforced
        info = prefix_affinity.parse_summary(summary)
        hashes = prefix_affinity.chain_hashes(a, summary['block'], 32)
        # The shared head's full block is resident and matchable by
        # the exact hash the LB computes.
        assert prefix_affinity.match_depth(hashes,
                                           info['hashes']) >= 1
    finally:
        eng.stop()
    off = _mk(params, cfg, prefix_share=False)
    try:
        assert off.prefix_summary() is None
    finally:
        off.stop()


def test_trie_duplicate_commit_dedups():
    t = paged_lib.BlockTrie(2)
    n = t.commit(None, (1, 2), 10)
    assert t.commit(None, (1, 2), 20) is None  # caller keeps its copy
    assert t.child(None, (1, 2)) is n


if __name__ == '__main__':
    raise SystemExit(pytest.main([__file__, '-v']))
