"""Numerics tests for the pallas flash-attention kernels (fwd + fused bwd).

Runs the kernels in pallas interpret mode on CPU (same lowering semantics,
no TPU needed) against the jnp reference and its ``jax.vjp`` — the oracle
the fused backward replaces. Block sizes are shrunk so the tests exercise
multi-block online softmax, the causally-skipped dk/dv grid cells, and the
split masked/unmasked loops.

Reference counterpart: the reference has no attention kernels of its own
(delegated to workloads, SURVEY.md §2.11); the oracle here plays the role
its workload-level kernels' unit tests play.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu.ops import attention


@pytest.fixture()
def small_blocks(monkeypatch):
    """Shrink kernel blocks so S=384 spans several blocks per kernel."""
    monkeypatch.setattr(attention, 'FWD_BLOCK_Q', 128)
    monkeypatch.setattr(attention, 'FWD_BLOCK_K', 128)
    monkeypatch.setattr(attention, 'DQ_BLOCK_Q', 128)
    monkeypatch.setattr(attention, 'DQ_BLOCK_K', 128)
    monkeypatch.setattr(attention, 'DKV_BLOCK', 128)


def _rand(shape, key, dtype=jnp.float32):
    return jax.random.normal(key, shape, dtype)


@pytest.mark.parametrize('causal', [True, False])
@pytest.mark.parametrize('group', [1, 2])
def test_flash_fwd_bwd_matches_reference_vjp(small_blocks, causal, group):
    b, hkv, s, d = 2, 2, 384, 64
    hq = hkv * group
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = _rand((b, hq, s, d), ks[0])
    k = _rand((b, hkv, s, d), ks[1])
    v = _rand((b, hkv, s, d), ks[2])
    g = _rand((b, hq, s, d), ks[3])

    o_ref, vjp_ref = jax.vjp(
        lambda a, b_, c: attention.attention_reference(a, b_, c, causal),
        q, k, v)
    o_pal, vjp_pal = jax.vjp(
        lambda a, b_, c: attention._flash_attention(a, b_, c, causal, True),
        q, k, v)

    assert jnp.allclose(o_ref, o_pal, atol=2e-2), 'forward mismatch'
    for name, a, b_ in zip(('dq', 'dk', 'dv'), vjp_ref(g), vjp_pal(g)):
        err = float(jnp.abs(a - b_).max())
        assert err < 5e-2, f'{name} max err {err}'


def test_flash_fwd_lse_is_logsumexp(small_blocks):
    b, h, s, d = 1, 2, 256, 64
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q, k, v = (_rand((b, h, s, d), kk) for kk in ks)
    _, lse = attention._flash_fwd(q, k, v, causal=False, interpret=True)
    scale = d ** -0.5
    logits = jnp.einsum('bhqd,bhkd->bhqk', q, k) * scale
    expect = jax.scipy.special.logsumexp(logits, axis=-1)[..., None]
    assert jnp.allclose(lse, expect, atol=1e-3)


def test_vmem_cap_takes_reference_and_says_so_once(monkeypatch, caplog):
    """Past the resident-K/V cap the whole op (fwd and bwd) takes the
    reference — on a trace-time condition, logged once per shape."""
    monkeypatch.setattr(attention, '_VMEM_CAP_ELEMS', 1)
    monkeypatch.setattr(attention, '_logged_fallbacks', set())
    b, h, s, d = 1, 2, 256, 64
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    q, k, v, g = (_rand((b, h, s, d), kk) for kk in ks)
    with caplog.at_level('WARNING', logger=attention.__name__):
        _, vjp = jax.vjp(
            lambda a, b_, c: attention.flash_attention(
                a, b_, c, True, interpret=True), q, k, v)
        attention.flash_attention(q, k, v, True, interpret=True)
    tagged = [r for r in caplog.records
              if attention.FALLBACK_TAG in r.getMessage()]
    assert len(tagged) == 1 and 'VMEM cap' in tagged[0].getMessage()
    _, vjp_ref = jax.vjp(
        lambda a, b_, c: attention.attention_reference(a, b_, c, True),
        q, k, v)
    for a, b_ in zip(vjp(g), vjp_ref(g)):
        assert jnp.allclose(a, b_, atol=1e-3)


def test_flash_gate_falls_back_on_unaligned_seq():
    """Sequence not divisible by 128 uses the reference path (no crash)."""
    b, h, s, d = 1, 2, 100, 64
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q, k, v = (_rand((b, h, s, d), kk) for kk in ks)
    out = attention.flash_attention(q, k, v, causal=True)
    ref = attention.attention_reference(q, k, v, causal=True)
    assert jnp.allclose(out, ref, atol=1e-5)


# -- pallas paged decode (ops/decode_attention.paged_decode) ----------------

_P, _MB, _NB = 16, 4, 13  # blocks of 16, max_len 64, 12 usable blocks

# name -> (valid lengths, block tables). 0 in a table is the junk sink:
# what the engine pads a row's unreserved tail with. The block of a live
# row's position valid - 1 (the one the step appends to) is in no other
# row's table below that row's length: the engine forks a shared tail.
_PAGED_CASES = {
    'len0-inactive': ([0, 33], [[0, 0, 0, 0], [3, 1, 2, 0]]),
    'len1': ([1, 1], [[5, 0, 0, 0], [6, 0, 0, 0]]),
    'len15': ([15], [[7, 0, 0, 0]]),
    'len16': ([16], [[7, 0, 0, 0]]),
    'len17': ([17], [[7, 2, 0, 0]]),
    'full-max-len': ([64, 64], [[1, 2, 3, 4], [8, 7, 6, 5]]),
    'ragged': ([5, 64, 0, 31, 48, 17],
               [[9, 0, 0, 0], [1, 2, 3, 4], [4, 4, 4, 4], [5, 6, 0, 0],
                [7, 8, 10, 0], [11, 12, 0, 0]]),
    # A row that finished mid-chunk decodes on past max_len until the
    # chunk ends: it attends its 64 positions, like the dense view.
    'past-max-len': ([64 + 3, 20], [[1, 2, 3, 4], [5, 6, 0, 0]]),
    # The prefix trie's case: two rows' tables name the same blocks
    # (whole ones: each row appends into a block of its own).
    'shared-blocks': ([40, 37, 33], [[2, 3, 4, 0], [2, 3, 5, 0],
                                     [2, 3, 6, 0]]),
    # Where the step's row lands (position valid - 1; groups are 2
    # blocks = 32 positions in these tests): the last row of a block
    # that ends a group, the first row of a fresh block that opens the
    # last group, and the second block of that group.
    'write-block-end': ([32, 48], [[3, 9, 0, 0], [1, 2, 4, 0]]),
    'write-group-start': ([33], [[7, 2, 5, 0]]),
    'write-mid-group': ([49, 50], [[1, 2, 3, 4], [8, 7, 6, 5]]),
    # An inactive row's stale table names a live row's blocks.
    'stale-inactive': ([0, 22, 0], [[5, 6, 0, 0], [5, 6, 0, 0],
                                    [6, 0, 0, 0]]),
    # Rows that finished mid-chunk decode on past their reservation:
    # both name the junk sink there and both write it, in slot order.
    'past-reservation': ([20, 37], [[5, 0, 0, 0], [6, 7, 0, 0]]),
}


def _paged_inputs(group, valid, tables, dtype, layer=1):
    """(q, k_new, v_new, k_pool, v_pool, layer, tables, valid): pools of
    three layers, of which ``layer`` is the one written and attended."""
    hkv, d = 2, 128
    key = jax.random.PRNGKey(len(valid) + group)
    q = jax.random.normal(key, (len(valid), hkv * group, d), dtype)
    kp, vp = (jax.random.normal(jax.random.fold_in(key, i),
                                (3, _NB, hkv, _P, d), dtype) for i in (1, 2))
    kn, vn = (jax.random.normal(jax.random.fold_in(key, i),
                                (len(valid), hkv, d), dtype) for i in (3, 4))
    return (q, kn, vn, kp, vp, jnp.int32(layer),
            jnp.asarray(tables, jnp.int32), jnp.asarray(valid, jnp.int32))


def _scattered(kn, vn, kp, vp, layer, tables, valid):
    """The pools as ``pool_write`` leaves them (what the step did before
    the kernel wrote, and S > 1 still does), less its junk: the scatter
    sends inactive rows to block 0 of layer 0, the kernel writes nothing
    for them."""
    from skypilot_tpu.models import paged
    return tuple(
        paged.pool_write(pool, layer, tables, valid - 1, new[:, :, None],
                         valid > 0).at[0, 0].set(pool[0, 0])
        for pool, new in ((kp, kn), (vp, vn)))


def _paged_reference(q, kn, vn, kp, vp, layer, tables, valid):
    """What the layer did before the kernel and still does off the
    TPU: the row scatter, then every row's whole table gathered into a
    dense view and the einsum path. -> (out, k_pool, v_pool)."""
    from skypilot_tpu.models import paged
    kp, vp = _scattered(kn, vn, kp, vp, layer, tables, valid)
    return (paged._gather_attention(  # noqa: SLF001 — oracle
        q[:, None], kp, vp, None, None, layer, tables, valid - 1)[:, 0],
            kp, vp)


def _bits(x):
    """An array's bit pattern: NaN compares equal to itself."""
    x = np.asarray(x)
    return x.view({2: np.uint16, 4: np.uint32}[x.dtype.itemsize])


@pytest.mark.parametrize('group', [1, 2], ids=['mha', 'gqa2'])
@pytest.mark.parametrize('case', list(_PAGED_CASES))
def test_paged_decode_matches_gather_path(monkeypatch, case, group):
    """float32 end to end, so the tolerance is the accumulation order's
    alone: 2e-5 on outputs of order 1; the pools come back as the row
    scatter leaves them, to the bit. Groups of 2 blocks make a full row
    loop twice and a 17-long row end mid-group."""
    from skypilot_tpu.ops import decode_attention

    monkeypatch.setattr(decode_attention, 'PAGED_GROUP', 2)
    valid, tables = _PAGED_CASES[case]
    args = _paged_inputs(group, valid, tables, jnp.float32)
    got, k_got, v_got = decode_attention.paged_decode(*args, interpret=True)
    want, k_want, v_want = _paged_reference(*args)
    got, live = np.asarray(got), np.asarray(valid) > 0
    np.testing.assert_allclose(got[live], np.asarray(want)[live],
                               atol=2e-5, rtol=2e-5)
    # A row with nothing valid reads nothing and returns zeros (the
    # gather path attends uniformly over junk there; neither is used).
    assert not got[~live].any()
    np.testing.assert_array_equal(_bits(k_got), _bits(k_want))
    np.testing.assert_array_equal(_bits(v_got), _bits(v_want))


# What the write must get right, by name -> (case, layer).
_WRITES = {
    'off-0-fresh-block': ('len17', 1),
    'off-last': ('write-block-end', 1),
    'clipped-past-max-len': ('past-max-len', 1),
    'inactive-writes-nothing': ('stale-inactive', 1),
    'layer-0': ('ragged', 0),
    'layer-2': ('ragged', 2),
    'group-boundary': ('write-group-start', 1),
    'second-block-of-last-group': ('write-mid-group', 1),
    'junk-sink-shared': ('past-reservation', 2),
}


@pytest.mark.parametrize('name', list(_WRITES))
def test_paged_decode_writes_what_the_scatter_wrote_bf16(monkeypatch, name):
    """bfloat16, the serving dtype, to the BIT: both pools equal the row
    scatter's (so every block no live row appends to, every other
    layer, block 0 and an inactive row's stale table's blocks hold what
    they held), and the output equals the same kernel's over pools the
    scatter wrote first (writing the same row again changes nothing):
    the token attends to itself in the values the pool now holds."""
    from skypilot_tpu.ops import decode_attention

    monkeypatch.setattr(decode_attention, 'PAGED_GROUP', 2)
    case, layer = _WRITES[name]
    valid, tables = _PAGED_CASES[case]
    q, kn, vn, kp, vp, l, t, n = _paged_inputs(2, valid, tables,
                                               jnp.bfloat16, layer)
    got, k_got, v_got = decode_attention.paged_decode(
        q, kn, vn, kp, vp, l, t, n, interpret=True)
    k_want, v_want = _scattered(kn, vn, kp, vp, l, t, n)
    want, k_again, v_again = decode_attention.paged_decode(
        q, kn, vn, k_want, v_want, l, t, n, interpret=True)
    for a, b in ((got, want), (k_got, k_want), (v_got, v_want),
                 (k_again, k_want), (v_again, v_want)):
        np.testing.assert_array_equal(_bits(a), _bits(b))
    # and it did write: each live row's position holds the new row
    for row, length in enumerate(valid):
        if length:
            at = min(length - 1, _MB * _P - 1) // _P, (length - 1) % _P
            np.testing.assert_array_equal(
                _bits(k_got[layer, tables[row][at[0]], :, at[1]]),
                _bits(kn[row]))
    others = [i for i in range(3) if i != layer]
    np.testing.assert_array_equal(_bits(k_got[others, ...]),
                                  _bits(kp[others, ...]))


@pytest.mark.parametrize('layer', [0, 2])
def test_paged_decode_reads_its_layers_named_blocks_only(layer):
    """The kernel is handed the WHOLE pools and a layer index. Every
    other layer, and every block of this one that no live row's table
    names below its length, holds NaN: one DMA off its address and the
    output says so (0 x NaN is NaN), and one write off its address and
    the pools say so. The gather path on the clean pools is the
    oracle."""
    from skypilot_tpu.ops import decode_attention

    valid, tables = _PAGED_CASES['ragged']
    q, kn, vn, kp, vp, l, t, n = _paged_inputs(2, valid, tables,
                                               jnp.float32, layer)
    want, k_want, v_want = _paged_reference(q, kn, vn, kp, vp, l, t, n)
    named = np.zeros((3, _NB), bool)
    for row, length in zip(tables, valid):
        named[layer, row[:-(-length // _P)]] = True
    poison = jnp.asarray(~named)[:, :, None, None, None]
    got, k_got, v_got = decode_attention.paged_decode(
        q, kn, vn, jnp.where(poison, jnp.nan, kp),
        jnp.where(poison, jnp.nan, vp), l, t, n, interpret=True)
    got = np.asarray(got)
    assert np.isfinite(got).all()
    live = np.asarray(valid) > 0
    np.testing.assert_allclose(got[live], np.asarray(want)[live],
                               atol=2e-5, rtol=2e-5)
    for pool, clean in ((k_got, k_want), (v_got, v_want)):
        np.testing.assert_array_equal(
            _bits(pool), _bits(jnp.where(poison, jnp.nan, clean)))


def test_paged_decode_bf16_tolerance():
    """bf16 pool and queries, the serving dtype: float32 logits, softmax
    and accumulation on both sides; the paths differ by when the
    probabilities are rounded to bf16 (before the normalisation here,
    after it there): 1e-2 on outputs of order 1."""
    from skypilot_tpu.ops import decode_attention

    valid, tables = _PAGED_CASES['ragged']
    args = _paged_inputs(2, valid, tables, jnp.bfloat16)
    got, _, _ = decode_attention.paged_decode(*args, interpret=True)
    assert got.dtype == jnp.bfloat16
    live = np.asarray(valid) > 0
    np.testing.assert_allclose(
        np.asarray(got, np.float32)[live],
        np.asarray(_paged_reference(*args)[0], np.float32)[live], atol=1e-2)


def test_paged_decode_geometry_gate():
    from skypilot_tpu.ops import decode_attention as da

    assert da.paged_fits(48, 128, 16, 128, jnp.bfloat16)  # the cells'
    assert da.paged_fits(4, 4, 16, 128, jnp.float32)
    assert not da.paged_fits(48, 128, 8, 128, jnp.bfloat16)   # half a tile
    assert da.paged_fits(48, 128, 8, 128, jnp.float32)
    assert not da.paged_fits(48, 128, 16, 64, jnp.bfloat16)   # D < a lane row
    assert not da.paged_fits(48, 128, 32, 128, jnp.int8)      # codes + scales
    assert not da.paged_fits(1024, 256, 16, 128, jnp.bfloat16)  # SMEM
    assert da._pick_group(128) == da.PAGED_GROUP
    assert da._pick_group(6) == 6 and da._pick_group(20) == 10


def test_paged_layer_takes_the_kernel_only_at_s1_on_float_pools(
        monkeypatch, caplog):
    """``paged.decode_path`` is the one rule: off the TPU the gather,
    silently; with the kernel asked for by name, S = 1 over a float pool
    that fits takes it, and a pool it cannot take says so once."""
    from skypilot_tpu.models import paged
    from skypilot_tpu.ops import decode_attention

    fit = ((4, 4), (_NB, 2, 16, 128), jnp.bfloat16)
    assert paged.decode_path(*fit, False) == 'gather'  # CPU, not asked
    monkeypatch.setattr(decode_attention, 'PAGED_INTERPRET', True)
    monkeypatch.setattr(attention, '_logged_fallbacks', set())
    assert paged.decode_path(*fit, False) == 'paged_kernel'
    with caplog.at_level('WARNING', logger=attention.__name__):
        for _ in range(2):
            assert paged.decode_path(*fit, True) == 'gather'  # int8
            assert paged.decode_path((4, 4), (_NB, 2, 16, 16),
                                     jnp.bfloat16, False) == 'gather'
    tagged = [r.getMessage() for r in caplog.records
              if attention.FALLBACK_TAG in r.getMessage()]
    assert len(tagged) == 2 and all('paged_decode' in t for t in tagged)

    # In the layer: S = 1 traces the kernel under its name, S = 2 (the
    # speculative verify, the shared-prefix prefill) keeps the gather.
    from skypilot_tpu.models import llama
    cfg = dataclasses.replace(llama.TINY, head_dim=128)
    params = jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), cfg))
    pool = jax.eval_shape(lambda: paged.init_pool(cfg, 2, 64, 9, 16))
    for s, named in ((1, True), (2, False)):
        jaxpr = str(jax.make_jaxpr(
            lambda p, t, c: paged.forward_paged(p, t, c, cfg))(
                params, jax.ShapeDtypeStruct((2, s), jnp.int32), pool))
        assert ('name=paged_decode' in jaxpr) == named, s
