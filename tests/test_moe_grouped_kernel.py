"""``ops/grouped_matmul.moe_gmm`` (the Pallas interpreter) against
``jax.lax.ragged_dot``, the XLA form of the same grouped matmul; the rule
that chooses between them (``moe.grouped_path``), and what the engine
reports of it. What Mosaic makes of the kernel at the cells' widths is
``tests/test_chip_compile.py``'s."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu.models import engine as engine_lib
from skypilot_tpu.models import mla_moe, model_ops, moe
from skypilot_tpu.ops import attention, decode_attention, grouped_matmul
from test_kda import (_engine, _picks_are_the_references, setup,
                      tokens)

# (sizes, rows, stack layers, layer, row tile); K = 256, N = 384
CASES = {
    # a tile shared by three groups; the groups end on a tile's edge
    'groups_in_tiles': ([5, 11, 16, 32], 64, 1, 0, 16),
    # held experts with no rows read nothing, wherever they lie
    'empty_groups': ([0, 7, 0, 0, 20, 0, 5, 0], 32, 1, 0, 16),
    # one group over three tiles and into a fourth, beside small ones
    'group_over_the_tile': ([3, 70, 1, 6], 80, 1, 0, 16),
    # rows past sum(sizes) (routed to the other chip, or junk): zeros
    'rows_past_the_groups': ([9, 0, 4, 2], 96, 1, 0, 16),
    'no_row_here': ([0, 0, 0, 0], 32, 1, 0, 16),
    # the whole scanned stack and a layer's index in it
    'stack_layer_0': ([6, 13, 0, 9], 64, 3, 0, 32),
    'stack_layer_2': ([6, 13, 0, 9], 64, 3, 2, 32),
}
K, N = 256, 384


@pytest.mark.parametrize('case', list(CASES))
def test_the_kernel_is_ragged_dot(case):
    """bf16 in, float32 accumulation, a bf16 result: ``ragged_dot``'s
    float32 product to within the result's own rounding (the sums go
    in another order), and EXACTLY zero past the groups."""
    sizes, m, n_l, layer, tm = CASES[case]
    e = len(sizes)
    ks = jax.random.split(jax.random.PRNGKey(len(case)), 2)
    lhs = jax.random.normal(ks[0], (m, K)).astype(jnp.bfloat16)
    rhs = jax.random.normal(ks[1], (n_l * e, K, N)).astype(jnp.bfloat16)
    sizes = jnp.asarray(sizes, jnp.int32)
    got = grouped_matmul.moe_gmm(lhs, rhs, sizes, jnp.int32(layer), tm=tm,
                                 interpret=True)
    assert got.shape == (m, N) and got.dtype == jnp.bfloat16
    mine = rhs[layer * e:(layer + 1) * e]
    want = jax.lax.ragged_dot(lhs, mine, sizes,
                              preferred_element_type=jnp.float32)
    total = int(sizes.sum())
    assert not np.asarray(got[total:], np.float32).any()
    got, want = np.asarray(got[:total], np.float32), np.asarray(want[:total])
    # half a bf16 ulp of the value, and of the largest where it is ~0
    room = 2.0 ** -8 * (np.abs(want) + np.abs(want).max(initial=0) / 64)
    assert (np.abs(got - want) <= room).all()


@pytest.mark.parametrize('mask', [False, True], ids=['all_rows', 'junk_rows'])
def test_the_expert_layer_is_the_same_through_either_path(mask,
                                                          monkeypatch):
    """``moe.dropfree_mlp`` over a stack, half the experts held here,
    through the kernel (the interpreter, asked for by name) and through
    ``ragged_dot``: the same output and counts; rows ``token_mask``
    leaves out (a freed slot still decodes) add nothing either way."""
    d, f, e, k, n = 128, 256, 8, 2, 48
    ks = jax.random.split(jax.random.PRNGKey(7), 6)
    layer = {
        'router': jax.random.normal(ks[0], (d, e)) * d ** -0.5,
        'router_bias': jnp.zeros((e,)),
        'we_gate': jax.random.normal(ks[1], (2, 4, d, f)) * d ** -0.5,
        'we_up': jax.random.normal(ks[2], (2, 4, d, f)) * d ** -0.5,
        'we_down': jax.random.normal(ks[3], (2, 4, f, d)) * f ** -0.5}
    x = jax.random.normal(ks[4], (n, d))
    keep = (jnp.asarray(np.random.default_rng(0).random(n) < 0.6)
            if mask else None)

    def run():
        return moe.dropfree_mlp(x, layer, k, 2.0, True, (0, 4), keep,
                                jnp.int32(1))

    assert moe.grouped_path(n * k, 4, d, f, x.dtype) == 'ragged_dot'
    want, want_load = run()
    monkeypatch.setattr(decode_attention, 'PAGED_INTERPRET', True)
    assert moe.grouped_path(n * k, 4, d, f, x.dtype) == 'kernel'
    jaxpr = str(jax.make_jaxpr(run)())
    assert 'moe_gmm' in jaxpr and 'ragged_dot' not in jaxpr
    got, load = run()
    assert np.array_equal(load, want_load)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5
    if mask:
        dropped = moe.dropfree_mlp(x.at[~keep].set(9.0), layer, k, 2.0,
                                   True, (0, 4), keep, jnp.int32(1))[0]
        assert np.array_equal(dropped[keep], got[keep])


def test_the_rule_takes_the_kernel_where_experts_get_many_rows(monkeypatch):
    """The cells' own calls: Kimi's decode step (48 slots x 8 over 128
    held experts: 3 rows an expert) and Xing's (48 x 4 over 64) keep
    ``ragged_dot``; a Kimi piece (512 x 8) and a Xing suffix prefill of
    128 tokens or more take the kernel; off the TPU, and at widths the
    kernel does not take, ``ragged_dot``."""
    bf16 = jnp.bfloat16
    kimi = dict(groups=128, k=2304, n=1024, dtype=bf16)
    xing = dict(groups=64, k=3584, n=1024, dtype=bf16)
    assert moe.grouped_path(4096, **kimi) == 'ragged_dot'     # the CPU
    monkeypatch.setattr(attention, '_use_pallas', lambda: True)
    assert moe.grouped_path(48 * 8, **kimi) == 'ragged_dot'
    assert moe.grouped_path(48 * 4, **xing) == 'ragged_dot'
    assert moe.grouped_path(512 * 8, **kimi) == 'kernel'
    assert moe.grouped_path(4 * 4096 * 8, **kimi) == 'kernel'
    assert moe.grouped_path(128 * 4, **xing) == 'kernel'
    assert moe.grouped_path(512 * 4, **xing) == 'kernel'
    # the down projection: [M, 1024] x [1024, width]
    assert moe.grouped_path(512 * 8, 128, 1024, 2304, bf16) == 'kernel'
    assert moe.grouped_path(4096, 128, 64, 1024, bf16) == 'ragged_dot'
    assert moe.grouped_path(4096, 128, 2304, 1024, jnp.int8) == 'ragged_dot'
    # the row tile: the rows an expert gets, 16 to 128
    assert [moe.row_tile(m, 128) for m in (384, 4096, 8192, 131072)] == [
        16, 32, 64, 128]
    assert grouped_matmul.gmm_fits(moe.row_tile(131072, 128), 3584, 1024,
                                   bf16)


def test_the_engine_reports_the_path_for_decode_and_prefill(monkeypatch):
    """``stats()['moe_grouped']`` through ``ModelOps.step_paths``: the
    rule asked at the engine's decode rows and its prefill piece (or
    whole prompt); the dense model reports nothing. On the CPU
    ``ragged_dot`` both; a wide model whose piece gives every expert 8
    rows, with the interpreter asked for by name: the kernel for the
    piece, and the picks it serves are the reference's."""
    from skypilot_tpu.models import llama
    cfg, fam, params, pcfg = setup()
    eng = _engine(params, pcfg, prefill_chunk=32)
    try:
        assert eng.stats()['moe_grouped'] == {'decode': 'ragged_dot',
                                              'prefill': 'ragged_dot'}
    finally:
        eng.stop()
    assert model_ops.ops_for(llama.TINY).step_paths(llama.TINY, None,
                                                    64) == {}
    assert mla_moe.step_paths(dataclasses.replace(
        mla_moe.TINY, num_experts=0, n_dense_layers=mla_moe.TINY.n_layers),
        None, 64) == {}

    cfg, fam, params, pcfg = setup(hidden_size=128,
                                   moe_intermediate_size=128)
    monkeypatch.setattr(decode_attention, 'PAGED_INTERPRET', True)
    engine_lib._jit_paged_chunk.clear_cache()
    programs = (mla_moe.jit_prefill, mla_moe.jit_paged_chunk,
                mla_moe.jit_paged_chunk_n)
    for p in programs:
        p.clear_cache()
    eng = _engine(params, pcfg, prefill_chunk=32)
    try:
        st = eng.stats()
        # 2 slots x 2 over 8 experts; a piece of 32 x 2 over 8
        assert st['moe_grouped'] == {'decode': 'ragged_dot',
                                     'prefill': 'kernel'}
        row = tokens(70, seed=3)
        out = eng.submit(list(map(int, row)), 4).result(timeout=600)
        _picks_are_the_references(fam, params, cfg, row, out)
    finally:
        eng.stop()
        for p in programs:
            p.clear_cache()
