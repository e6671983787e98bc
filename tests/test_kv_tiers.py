"""Hierarchical KV memory (serve/kv_tiers.py, ISSUE 20).

Corruption contract under test: every byte is crc32-checked at the
tier boundary; torn/truncated/bit-flipped spill segments must degrade
to recompute with the chain quarantined — never a failed request,
never an engine-thread raise — and a partial segment file must be
invisible to the index on reload (the same invariants the ckpt
torn-write tests enforce). Plus the HostPool decayed-hotness LRU and
the end-to-end engine fallback at greedy byte parity.
"""
import os
import time

import numpy as np
import pytest

from skypilot_tpu.serve import kv_tiers


def _tiers(host_bytes=1 << 20, spill_dir='', fetch_max=2):
    return kv_tiers.KVTiers(block=4, n_layers=2, n_kv_heads=1,
                            head_dim=3, quantized=True,
                            host_bytes=host_bytes, spill_dir=spill_dir,
                            fetch_max=fetch_max)


def _entry(tiers, digest, row, seed=0):
    rng = np.random.default_rng(seed)
    planes = []
    for name, (shape, dtype) in tiers._plane_spec.items():
        if dtype == 'int8':
            arr = rng.integers(-8, 8, size=shape).astype(np.int8)
        else:
            arr = rng.standard_normal(shape).astype(np.float32)
        planes.append(tiers._plane(name, arr))
    return kv_tiers.TierEntry(digest, list(row), planes)


# ---------------------------------------------------------------------------
# HostPool


def test_host_pool_accounting_and_pop():
    t = _tiers()
    pool = t._host
    a = _entry(t, b'a' * 8, range(4), seed=1)
    b = _entry(t, b'b' * 8, range(8), seed=2)
    pool.insert(a)
    pool.insert(b)
    assert pool.bytes == a.nbytes + b.nbytes
    assert b'a' * 8 in pool and b'b' * 8 in pool
    got = pool.pop(b'a' * 8)
    assert got is a and pool.bytes == b.nbytes
    assert pool.pop(b'missing!') is None and pool.bytes == b.nbytes


def test_host_pool_decayed_hotness_protects_hot_oldtimer():
    """Pure insertion-order LRU would flush an early HOT chain behind
    a drive-by scan of one-shot prefixes; the decayed-hotness pick
    must evict the never-hit newcomer instead."""
    t = _tiers()
    pool = t._host
    hot = _entry(t, b'hot_8byt', range(4), seed=1)
    pool.insert(hot)
    for _ in range(4):
        pool.touch(hot.digest)
    cold = _entry(t, b'cold8byt', range(4), seed=2)
    pool.insert(cold)
    evicted = pool.evict_cold()
    assert evicted is cold
    assert hot.digest in pool


# ---------------------------------------------------------------------------
# SpillStore: segment format + torn-write invariants


def test_spill_segment_roundtrip_range_read(tmp_path):
    t = _tiers()
    store = kv_tiers.SpillStore(str(tmp_path))
    e1 = _entry(t, b'digest_1', range(4), seed=1)
    e2 = _entry(t, b'digest_2', range(8), seed=2)
    want = {e.digest: {p['name']: p['data'] for p in e.planes}
            for e in (e1, e2)}
    path = store.write_segment([e1, e2])
    assert path is not None and os.path.exists(path)
    store.admit(path, [e1, e2])
    assert store.bytes == e1.nbytes + e2.nbytes
    cache = {}
    for digest in (e1.digest, e2.digest):
        p, rec = store.index[digest]
        planes = kv_tiers.SpillStore.read_entry(p, rec, cache)
        assert {pl['name']: pl['data']
                for pl in planes} == want[digest]
    # A fresh index rebuilt from disk serves the same ranges.
    store2 = kv_tiers.SpillStore(str(tmp_path))
    assert store2.load_index() == 2 and store2.load_errors == 0
    p, rec = store2.index[e1.digest]
    planes = kv_tiers.SpillStore.read_entry(p, rec, {})
    assert {pl['name']: pl['data'] for pl in planes} == want[e1.digest]


def test_truncated_segment_invisible_on_reload(tmp_path):
    """A segment whose advertised payload extents exceed the file size
    was torn mid-write: NOTHING in it may be indexed (whole-or-nothing
    per file)."""
    t = _tiers()
    store = kv_tiers.SpillStore(str(tmp_path))
    path = store.write_segment([_entry(t, b'digest_1', range(4))])
    size = os.path.getsize(path)
    with open(path, 'r+b') as f:
        f.truncate(size - 7)
    store2 = kv_tiers.SpillStore(str(tmp_path))
    assert store2.load_index() == 0
    assert store2.load_errors == 1
    assert b'digest_1' not in store2


def test_bad_magic_and_garbage_segments_invisible_on_reload(tmp_path):
    t = _tiers()
    store = kv_tiers.SpillStore(str(tmp_path))
    path = store.write_segment([_entry(t, b'digest_1', range(4))])
    with open(path, 'r+b') as f:
        f.write(b'XXXX')  # clobber the magic
    (tmp_path / ('junk' + kv_tiers.SEG_SUFFIX)).write_bytes(b'\x00' * 16)
    # A leftover .tmp from a crashed writer is not even a candidate.
    (tmp_path / 'seg-dead.seg.tmp').write_bytes(b'partial')
    store2 = kv_tiers.SpillStore(str(tmp_path))
    assert store2.load_index() == 0
    assert store2.load_errors == 2  # clobbered + junk; .tmp ignored


def test_bitflip_payload_fails_crc_on_range_read(tmp_path):
    t = _tiers()
    store = kv_tiers.SpillStore(str(tmp_path))
    e = _entry(t, b'digest_1', range(4), seed=3)
    path = store.write_segment([e])
    store.admit(path, [e])
    _p, rec = store.index[e.digest]
    # Flip one payload byte of the first plane.
    base = len(kv_tiers.SEG_MAGIC) + kv_tiers._LEN.size
    with open(path, 'r+b') as f:
        head = f.read(base)
        (hlen,) = kv_tiers._LEN.unpack_from(head, len(kv_tiers.SEG_MAGIC))
        off = base + hlen + int(rec['planes'][0]['offset'])
        f.seek(off)
        byte = f.read(1)
        f.seek(off)
        f.write(bytes([byte[0] ^ 0xFF]))
    with pytest.raises(ValueError, match='crc32 mismatch'):
        kv_tiers.SpillStore.read_entry(path, rec, {})


# ---------------------------------------------------------------------------
# KVTiers: quarantine + recompute-fallback plumbing (no engine)


def test_fetch_of_corrupt_segment_quarantines_chain(tmp_path):
    """A background fetch hitting a bit-flipped range must quarantine
    the digest (later lookups miss => recompute), count the corruption,
    and still fire the completion callback — the parked request is
    re-queued either way."""
    t = _tiers(spill_dir=str(tmp_path))
    e = _entry(t, b'digest_1', range(4), seed=4)
    t._spill_entries([e])
    assert t.lookup(e.digest) == 'spilled'
    path, _rec = t._spill.index[e.digest]
    with open(path, 'r+b') as f:
        f.seek(-1, os.SEEK_END)
        last = f.read(1)
        f.seek(-1, os.SEEK_END)
        f.write(bytes([last[0] ^ 0xFF]))
    done = []
    assert t.request_fetch([e.digest],
                           lambda digests, ok: done.append(ok))
    assert t.quiesce(10)
    t.stop()
    assert done == [False]
    st = t.stats()
    assert st['corrupt'] == 1 and st['quarantined'] == 1, st
    assert t.lookup(e.digest) is None  # recompute from here on
    assert e.digest not in t._spill  # the bad range is deindexed
    # The drained segment file is garbage-collected.
    deadline = time.time() + 5
    while os.path.exists(path) and time.time() < deadline:
        time.sleep(0.02)
    assert not os.path.exists(path)


def test_fetch_of_clean_segment_reloads_to_host(tmp_path):
    t = _tiers(spill_dir=str(tmp_path))
    e = _entry(t, b'digest_1', range(4), seed=5)
    t._spill_entries([e])
    done = []
    assert t.request_fetch([e.digest],
                           lambda digests, ok: done.append(ok))
    assert t.quiesce(10)
    t.stop()
    assert done == [True]
    assert t.lookup(e.digest) == 'host'
    st = t.stats()
    assert st['reloads'] == 1 and st['spill_hits'] == 1, st


def test_take_for_promote_corrupt_entry_truncates_and_quarantines():
    """Promotion claims a chain-contiguous head: a corrupt middle
    entry is quarantined, the head before it still promotes, and the
    tail after it stays host-resident (recompute covers the gap)."""
    t = _tiers()
    entries = [_entry(t, bytes([65 + i]) * 8, range(4 * (i + 1)),
                      seed=10 + i) for i in range(3)]
    for e in entries:
        t._host.insert(e)
    # Bit-flip the middle entry's first plane payload.
    p0 = entries[1].planes[0]
    p0['data'] = bytes([p0['data'][0] ^ 0xFF]) + p0['data'][1:]
    got = t.take_for_promote([e.digest for e in entries])
    assert len(got) == 1
    assert set(got[0]) == {'k', 'v', 'k_s', 'v_s'}
    st = t.stats()
    assert st['corrupt'] == 1 and st['quarantined'] == 1, st
    assert t.lookup(entries[1].digest) is None
    assert t.lookup(entries[2].digest) == 'host'  # untouched tail
    # A shape/dtype mismatch is rejected by the same gate.
    bad = _entry(t, b'digest_z', range(4), seed=20)
    bad.planes[0]['shape'] = [1, 1, 1, 1]
    bad.planes[0]['data'] = bad.planes[0]['data'][:12]
    bad.planes[0]['nbytes'] = 12
    bad.planes[0]['crc32'] = kv_tiers._crc(bad.planes[0]['data'])
    t._host.insert(bad)
    assert t.take_for_promote([bad.digest]) == []
    assert t.lookup(bad.digest) is None


def test_advert_entries_tier_tags_and_exclusion(tmp_path):
    t = _tiers(spill_dir=str(tmp_path))
    host_e = _entry(t, b'digest_h', range(4), seed=6)
    t._host.insert(host_e)
    spill_e = _entry(t, b'digest_s', range(8), seed=7)
    t._spill_entries([spill_e])
    rows, truncated = t.advert_entries(8, set())
    assert not truncated
    by_hex = {r[0]: r for r in rows}
    assert by_hex[host_e.digest.hex()][2] == 1
    assert by_hex[spill_e.digest.hex()][2] == 2
    assert by_hex[host_e.digest.hex()][1] == 1   # depth in blocks
    assert by_hex[spill_e.digest.hex()][1] == 2
    rows, _ = t.advert_entries(8, {host_e.digest.hex()})
    assert [r[0] for r in rows] == [spill_e.digest.hex()]
    rows, truncated = t.advert_entries(0, set())
    assert rows == [] and truncated
    t.stop()


def test_resolve_rows_covers_host_and_spill(tmp_path):
    t = _tiers(spill_dir=str(tmp_path))
    host_e = _entry(t, b'digest_h', [1, 2, 3, 4], seed=8)
    t._host.insert(host_e)
    spill_e = _entry(t, b'digest_s', [1, 2, 3, 4, 5, 6, 7, 8], seed=9)
    t._spill_entries([spill_e])
    rows = t.resolve_rows([b'digest_h', b'digest_s', b'digest_x'])
    assert rows == {b'digest_h': [1, 2, 3, 4],
                    b'digest_s': [1, 2, 3, 4, 5, 6, 7, 8]}
    t.stop()


# ---------------------------------------------------------------------------
# End-to-end: engine recompute fallback at greedy byte parity


@pytest.fixture(scope='module')
def tiny():
    import jax
    from skypilot_tpu.models import llama
    cfg = llama.TINY
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def test_engine_corrupt_spill_degrades_to_recompute(tiny, tmp_path,
                                                    monkeypatch):
    """Pool pressure demotes + spills chains; every spill segment is
    then bit-flipped on disk. Resubmitting the evicted prompts must
    stay byte-exact (recompute fallback), fail NO request, and
    quarantine the corrupt chains."""
    from skypilot_tpu.models import engine as engine_lib, generate
    cfg, params = tiny
    monkeypatch.setenv('SKYTPU_KV_SPILL_DIR', str(tmp_path))
    monkeypatch.setenv('SKYTPU_KV_HOST_BYTES', '1')  # spill everything

    def solo(row, n):
        out = generate.generate(params, cfg,
                                np.asarray([row], np.int32),
                                max_new_tokens=n, max_len=64)
        return np.asarray(out[0]).tolist()

    eng = engine_lib.ContinuousEngine(params, cfg, slots=4, max_len=64,
                                      chunk_steps=2, kv_blocks=5)
    eng.start()
    try:
        heads = [[((17 * h + j) % 250) + 1 for j in range(24)]
                 for h in range(3)]
        for h in heads:
            row = h + [5, 6, 7, 8]
            assert eng.submit(row, 6).result(timeout=300) == \
                solo(row, 6)
        assert eng._kv_tiers.quiesce(20)
        assert eng.stats()['kv_tiers']['spills'] >= 1
        # Flip one payload byte in EVERY visible segment file.
        segs = [p for p in os.listdir(tmp_path)
                if p.endswith(kv_tiers.SEG_SUFFIX)]
        assert segs
        for name in segs:
            path = tmp_path / name
            with open(path, 'r+b') as f:
                f.seek(-1, os.SEEK_END)
                last = f.read(1)
                f.seek(-1, os.SEEK_END)
                f.write(bytes([last[0] ^ 0xFF]))
        for h in heads:
            row = h + [9, 9, 9]
            assert eng.submit(row, 6).result(timeout=300) == \
                solo(row, 6)
        assert eng._kv_tiers.quiesce(20)
        st = eng.stats()['kv_tiers']
        assert st['corrupt'] >= 1, st
        assert st['quarantined'] >= 1, st
    finally:
        eng.stop()
