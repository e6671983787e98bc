"""models/mla_moe.py against the plain reference (benchmarks/reference/
mla_moe.py) on seeded weights at a tiny size: 4 residual streams, 8
experts top-2 plus a shared one, one dense and two expert layers.
Float32 here so that the comparison is of the mathematics (the served
path in bfloat16 against the same reference is the chip's, PERF.md)."""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import manifest, weights
from skypilot_tpu.models import engine as engine_lib
from skypilot_tpu.models import generate, llama, mla_moe, model_ops, moe
from skypilot_tpu.ops import decode_attention

DATA = os.path.join(os.path.dirname(__file__), 'benchmarks', 'data')
TOL = 2e-4


def setup(seed=5, **changes):
    with open(os.path.join(DATA, 'tiny_mla_moe_config.json')) as f:
        cfg = json.load(f)
    cfg.update(changes)
    fam = manifest.family(cfg)
    fam.check(cfg)
    params = jax.tree.map(lambda x: x.astype(jnp.float32),
                          weights.make_params(cfg, seed))
    pcfg = dataclasses.replace(fam.program_config(cfg), dtype=jnp.float32)
    return cfg, fam, params, pcfg


def tokens(n, seed=0, vocab=512):
    return np.random.default_rng(seed).integers(0, vocab, size=(n,))


def test_full_forward_is_the_references():
    cfg, fam, params, pcfg = setup()
    toks = tokens(32)
    want = fam.reference.logits_at(params, toks, np.arange(32), cfg)
    # a cache exactly as wide as the prompt: the fresh (flash) path
    got, _ = mla_moe.forward_cached(params, toks[None],
                                    mla_moe.init_cache(pcfg, 1, 32), pcfg,
                                    all_logits=True)
    assert float(jnp.max(jnp.abs(got[0] - want))) < TOL
    # a wider one: the masked expanded path over the row's view
    got, _ = mla_moe.forward_cached(params, toks[None],
                                    mla_moe.init_cache(pcfg, 1, 48), pcfg,
                                    all_logits=True)
    assert float(jnp.max(jnp.abs(got[0] - want))) < TOL


def _paged_run(params, pcfg, toks, n_prompt, monkeypatch=None):
    """Prefill ``toks[:n_prompt]`` densely, insert it into a pool, then
    decode the rest one token a step: logits at every position."""
    from skypilot_tpu.models import paged
    block, n_blocks = 16, 8
    cache = mla_moe.init_cache(pcfg, 1, 16)
    logits, cache = mla_moe.forward_cached(
        params, toks[None, :n_prompt], cache, pcfg,
        jnp.asarray([n_prompt], jnp.int32))
    pool = mla_moe.init_pool(pcfg, 2, 64, n_blocks, block)
    table = np.zeros((1, 4), np.int32)
    table[0, :3] = [5, 2, 7]
    pool = paged.jit_insert(pool, cache, table, np.asarray([1], np.int32))
    out = [logits[0]]
    last = np.zeros((2,), np.int32)
    # skylint: allow-jit(test-only)
    step = jax.jit(lambda p, t, c: mla_moe.forward_paged(
        p, t, c, pcfg, active_rows=jnp.asarray([False, True])))
    for i in range(n_prompt, len(toks)):
        last[1] = toks[i]
        # A copy: the CPU backend may alias a numpy buffer and run the
        # step after the next turn has already rewritten ``last``.
        logits, pool, load = step(params, jnp.asarray(last.copy())[:, None],
                                  pool)
        out.append(logits[1])
    return jnp.stack(out), load


@pytest.mark.parametrize('kernel', [False, True], ids=['gather', 'kernel'])
def test_prefill_then_paged_decode_gives_the_references_logits(
        kernel, monkeypatch):
    cfg, fam, params, pcfg = setup()
    if kernel:   # the Mosaic kernel in the interpreter, asked for by name
        monkeypatch.setattr(decode_attention, 'PAGED_INTERPRET', True)
    pool_shape = (3, 8, 1, 16, pcfg.latent_width)
    assert mla_moe.decode_path((2, 4), pool_shape, jnp.float32) == (
        'mla_kernel' if kernel else 'gather')
    toks = tokens(24, seed=3)
    got, load = _paged_run(params, pcfg, toks, 11)
    want = fam.reference.logits_at(params, toks, np.arange(10, 24), cfg)
    assert float(jnp.max(jnp.abs(got - want))) < TOL
    # one live row, two expert layers, top-2: four (token, choice) pairs
    assert int(load.sum()) == 4


def test_absorbed_attention_is_expanded_attention():
    _, _, params, pcfg = setup()
    layer = jax.tree.map(lambda x: x[0], params['moe'])
    key = jax.random.PRNGKey(1)
    h = jax.random.normal(key, (3, 1, pcfg.d_model))
    view = jax.random.normal(jax.random.fold_in(key, 1),
                             (3, 24, pcfg.latent_width))
    view = view.at[..., pcfg.latent_dim:].set(0)
    valid = jnp.asarray([24, 7, 1], jnp.int32)
    positions = (valid - 1)[:, None]
    q, _ = mla_moe._q_and_latent(pcfg, h, layer, positions)
    expanded = mla_moe._attend_view(pcfg, q, view, layer, positions, valid)
    absorbed = mla_moe._unabsorb(pcfg, mla_moe._absorbed_view(
        pcfg, mla_moe._absorb(pcfg, q[:, 0], layer), view, valid), layer)
    assert float(jnp.max(jnp.abs(expanded[:, 0] - absorbed))) < 1e-5


def test_the_kernel_reads_what_the_dense_view_reads():
    """``mla_decode`` (interpreter) against the jnp step over the
    gathered view, at a width with a padded tail (40 of 128)."""
    _, _, _, pcfg = setup()
    key = jax.random.PRNGKey(2)
    pool = jax.random.normal(key, (3, 9, 1, 16, pcfg.latent_width))
    pool = pool.at[..., pcfg.latent_dim:].set(0)
    tables = jnp.asarray([[3, 1, 8, 0], [2, 5, 0, 0], [4, 0, 0, 0]])
    valid = jnp.asarray([40, 17, 0], jnp.int32)
    q = jax.random.normal(jax.random.fold_in(key, 1),
                          (3, pcfg.n_heads, pcfg.latent_dim))
    got = decode_attention.mla_decode(
        q, pool, jnp.int32(1), tables, valid, pcfg.kv_lora_rank,
        mla_moe.softmax_scale(pcfg), interpret=True)
    want = mla_moe._absorbed_view(pcfg, q, mla_moe._pool_view(pool, 1,
                                                              tables), valid)
    assert float(jnp.max(jnp.abs(got[:2] - want[:2]))) < 1e-5
    assert float(jnp.max(jnp.abs(got[2]))) == 0.0   # valid 0 reads nothing


def test_sinkhorn_rows_and_columns_sum_to_one():
    m = jnp.exp(jax.random.normal(jax.random.PRNGKey(0), (5, 7, 4, 4)))
    out = mla_moe.sinkhorn(m, 20, 1e-6)
    assert float(jnp.max(jnp.abs(out.sum(-1) - 1))) < 1e-3
    assert float(jnp.max(jnp.abs(out.sum(-2) - 1))) < 1e-5
    assert float(out.min()) > 0


def test_the_selection_bias_changes_the_experts_and_not_the_weights():
    key = jax.random.PRNGKey(4)
    x = jax.random.normal(key, (16, 64))
    router = jax.random.normal(jax.random.fold_in(key, 1), (64, 8)) / 8
    idx0, w0 = moe.route_sigmoid(x, router, jnp.zeros((8,)), 2, 2.0)
    bias = jnp.zeros((8,)).at[5].set(10.0)
    idx1, w1 = moe.route_sigmoid(x, router, bias, 2, 2.0)
    assert bool(jnp.all(jnp.any(idx1 == 5, axis=-1)))       # always taken
    assert not bool(jnp.all(jnp.any(idx0 == 5, axis=-1)))
    # its weight is its own score's share, not the biased one's
    s = jax.nn.sigmoid(x @ router)
    sel = jnp.take_along_axis(s, idx1, axis=-1)
    want = 2.0 * sel / sel.sum(-1, keepdims=True)
    assert float(jnp.max(jnp.abs(w1 - want))) < 1e-6
    assert float(jnp.max(jnp.abs(w1.sum(-1) - 2.0))) < 1e-6


def _expert_layer(seed=0, e=8, d=64, f=32, shared=True):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    p = {'router': jax.random.normal(ks[0], (d, e)) / d ** 0.5,
         'router_bias': jnp.zeros((e,)),
         'we_gate': jax.random.normal(ks[1], (e, d, f)) / d ** 0.5,
         'we_up': jax.random.normal(ks[2], (e, d, f)) / d ** 0.5,
         'we_down': jax.random.normal(ks[3], (e, f, d)) / f ** 0.5}
    if shared:
        p.update(ws_gate=jax.random.normal(ks[4], (d, f)) / d ** 0.5,
                 ws_up=jax.random.normal(ks[5], (d, f)) / d ** 0.5,
                 ws_down=jax.random.normal(ks[6], (f, d)) / f ** 0.5)
    return p


def _per_token(x, p, top_k, scale):
    """Every token through its own experts, one at a time."""
    idx, w = moe.route_sigmoid(x, p['router'], p['router_bias'], top_k,
                               scale)
    out = []
    for t in range(x.shape[0]):
        y = 0
        for j in range(top_k):
            e = int(idx[t, j])
            y = y + w[t, j] * ((jax.nn.silu(x[t] @ p['we_gate'][e])
                                * (x[t] @ p['we_up'][e])) @ p['we_down'][e])
        out.append(y)
    return jnp.stack(out)


@pytest.mark.parametrize('n', [1, 5, 64])
def test_no_token_is_dropped_at_any_batch_or_skew(n):
    """Every token to the same two experts (16x the even load at 64
    tokens over 8 experts): each still gets both."""
    p = _expert_layer(shared=False)
    p['router_bias'] = jnp.zeros((8,)).at[jnp.asarray([2, 6])].set(10.0)
    x = jax.random.normal(jax.random.PRNGKey(9), (n, 64))
    got, load = moe.dropfree_mlp(x, p, 2, 2.0)
    assert load.tolist() == [0, 0, n, 0, 0, 0, n, 0]
    assert float(jnp.max(jnp.abs(got - _per_token(x, p, 2, 2.0)))) < 1e-4


@pytest.mark.parametrize('ways', [2, 4])
def test_expert_shares_add_up_to_the_uncut_layer(ways):
    """Each share routes over all 8 experts and adds its own experts'
    part; the shared expert is every share's, counted once."""
    p = _expert_layer(seed=3)
    x = jax.random.normal(jax.random.PRNGKey(8), (12, 64))
    whole, load = moe.dropfree_mlp(x, p, 2, 2.0)
    only_shared = {k: v for k, v in p.items() if not k.startswith('we_')}
    g = x @ p['ws_gate']
    shared = (jax.nn.silu(g) * (x @ p['ws_up'])) @ p['ws_down']
    total, per = 0, 8 // ways
    for i in range(ways):
        lo, hi = i * per, (i + 1) * per
        share = dict(only_shared, **{k: p[k][lo:hi] for k in
                                     ('we_gate', 'we_up', 'we_down')})
        y, load_i = moe.dropfree_mlp(x, share, 2, 2.0, held=(lo, hi))
        assert load_i.tolist() == load.tolist()     # routing is global
        total = total + (y - shared)
    assert float(jnp.max(jnp.abs(total + shared - whole))) < 1e-4


def test_a_layer_of_a_stack_is_the_layer_sliced_out():
    """``stack_layer``: the whole stack as L x E groups, one layer's
    live, gives what that layer's own experts give."""
    layers = [_expert_layer(seed=s) for s in (1, 2, 3)]
    x = jax.random.normal(jax.random.PRNGKey(7), (9, 64))
    want, _ = moe.dropfree_mlp(x, layers[1], 2, 2.0)
    stacked = dict(layers[1], **{k: jnp.stack([p[k] for p in layers])
                                 for k in ('we_gate', 'we_up', 'we_down')})
    got, _ = moe.dropfree_mlp(x, stacked, 2, 2.0, stack_layer=jnp.int32(1))
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5


def test_junk_rows_reach_no_expert_and_no_count():
    p = _expert_layer(seed=5)
    x = jax.random.normal(jax.random.PRNGKey(6), (6, 64))
    mask = jnp.asarray([True, False, True, True, False, True])
    got, load = moe.dropfree_mlp(x, p, 2, 2.0, token_mask=mask)
    want, load_live = moe.dropfree_mlp(x[mask], p, 2, 2.0)
    assert float(jnp.max(jnp.abs(got[mask] - want))) < 1e-5
    assert load.tolist() == load_live.tolist() and int(load.sum()) == 8


def test_one_stream_and_no_experts_is_a_plain_pre_norm_block():
    """``hc_mult`` 1 without experts: no map is made or stored, and the
    block is x + attn(norm(x)), then x + swiglu(norm(x))."""
    cfg, fam, params, pcfg = setup(hc_mult=1, n_routed_experts=0,
                                   first_k_dense_replace=3)
    assert 'moe' not in params
    assert not [k for k in params['dense'] if k.startswith('hc_')]
    toks = tokens(24, seed=2)
    want = fam.reference.logits_at(params, toks, np.arange(24), cfg)
    got, _ = mla_moe.forward_cached(params, toks[None],
                                    mla_moe.init_cache(pcfg, 1, 24), pcfg,
                                    all_logits=True)
    assert float(jnp.max(jnp.abs(got[0] - want))) < TOL
    # and one layer by hand, with the program's own attention
    cfg, fam, params, pcfg = setup(hc_mult=1, n_routed_experts=0,
                                   num_hidden_layers=1,
                                   first_k_dense_replace=1)
    got, _ = mla_moe.forward_cached(params, toks[None],
                                    mla_moe.init_cache(pcfg, 1, 24), pcfg,
                                    all_logits=True)
    layer = jax.tree.map(lambda x: x[0], params['dense'])
    x = params['embed'][toks][None]
    h = llama.rms_norm(x, layer['attn_norm'], pcfg.norm_eps)
    q, latent = mla_moe._q_and_latent(pcfg, h, layer, jnp.arange(24)[None])
    x = x + mla_moe._wo(mla_moe._attend_fresh(pcfg, q, latent, layer), layer)
    h = llama.rms_norm(x, layer['mlp_norm'], pcfg.norm_eps)
    x = x + (jax.nn.silu(h @ layer['w_gate']) * (h @ layer['w_up'])
             ) @ layer['w_down']
    x = llama.rms_norm(x, params['final_norm'], pcfg.norm_eps)
    assert float(jnp.max(jnp.abs(got - x @ params['lm_head']))) < 1e-4


def test_generate_serves_the_model_through_the_table():
    """``generate.generate`` (dense cache: prefill, then the absorbed
    step) picks what the reference's full forward picks."""
    cfg, fam, params, pcfg = setup()
    prompt = tokens(12, seed=6)
    out = np.asarray(generate.generate(params, pcfg,
                                       jnp.asarray(prompt)[None], 6))[0]
    seq = np.concatenate([prompt, out])
    logits = fam.reference.logits_at(params, seq[:-1], np.arange(11, 17), cfg)
    assert np.asarray(logits.argmax(-1)).tolist() == out.tolist()


# -- through the engine ------------------------------------------------------


def _serve(engine, rows, max_new=6):
    futs = [engine.submit(list(map(int, r)), max_new) for r in rows]
    return [f.result(timeout=300) for f in futs]


def test_the_engine_serves_shared_prefixes_and_forks_of_the_latent_pool():
    """Requests over the paged latent pool, pipelined, with block
    sharing: a cold prompt, one that shares its full blocks (the
    shared-prefix prefill), and one that diverges INSIDE a committed
    block (a copy-on-write fork). Every served token is the
    reference's pick; the counters the benchmark reads are there."""
    cfg, fam, params, pcfg = setup()
    eng = engine_lib.ContinuousEngine(
        params, pcfg, slots=4, max_len=96, kv_blocks=25,
        kv_block=16, prefill_batch=2, chunk_steps=4, prefix_share=True,
        kv_tiers=False, kv_quantize=False)
    try:
        assert eng.pipeline_depth == 1 and eng.prefix_share
        base = tokens(50, seed=11)
        rows = [base, np.concatenate([base[:32], tokens(9, seed=12)]),
                np.concatenate([base[:37], tokens(5, seed=13)])]
        outs = [_serve(eng, [r])[0] for r in rows]
        for row, out in zip(rows, outs):
            seq = np.concatenate([row, out])
            logits = fam.reference.logits_at(
                params, seq[:-1], np.arange(len(row) - 1, len(seq) - 1), cfg)
            best = np.asarray(logits.max(-1))
            got = np.asarray(logits)[np.arange(len(out)), out]
            assert float(np.max(best - got)) < TOL, (best - got)
        st = eng.stats()
        assert st['prefix_share']['hits'] == 2
        assert st['prefix_share']['cow_forks'] >= 1
        assert st['prefill_tokens_saved'] >= 32 + 37
        assert st['kv_bytes_per_token'] == 3 * (32 + 8) * 4   # float32 here
        assert st['decode_attention'] == 'gather'
        # 2 expert layers x top-2 a decoded token; prefill is not counted
        assert st['moe_tokens_routed'] == sum(st['moe_expert_load']) > 0
        assert st['moe_tokens_routed'] % 4 == 0
        assert st['moe_expert_load_max'] >= st['moe_expert_load_mean'] > 0
    finally:
        eng.stop()


@pytest.mark.parametrize('kwargs, feature', [
    (dict(kv_quantize=True), 'kv_quantize'),
    (dict(kv_tiers=True), 'kv_tiers'),
    (dict(prefill_chunk=64), 'prefill_chunk'),
    (dict(draft_params={}, draft_cfg=llama.TINY), 'speculative decoding')],
    ids=lambda v: v if isinstance(v, str) else '')
def test_what_the_latent_cache_does_not_do_is_refused_by_name(kwargs,
                                                              feature):
    _, _, params, pcfg = setup()
    base = dict(slots=2, max_len=64, kv_blocks=9)
    with pytest.raises(ValueError, match=feature):
        engine_lib.ContinuousEngine(params, pcfg, **dict(base, **kwargs))


def test_handoff_is_refused_and_tiers_default_off_for_the_latent_cache():
    _, _, params, pcfg = setup()
    eng = engine_lib.ContinuousEngine(params, pcfg, slots=2, max_len=64,
                                      kv_blocks=9)
    try:
        assert eng._kv_tiers is None and eng.prefix_share
        with pytest.raises(ValueError, match='KV handoff'):
            eng.submit_prefill([1, 2, 3], 4)
        with pytest.raises(ValueError, match='KV handoff'):
            eng.submit_import([1, 2, 3], 4, 5)
    finally:
        eng.stop()


def test_the_engine_asks_the_table_not_the_config():
    """No ``num_experts`` test is left in the engine; the Llama row
    names the functions the engine called before there was a table."""
    from skypilot_tpu.models import paged
    with open(engine_lib.__file__) as f:
        assert 'num_experts' not in f.read()
    ops = model_ops.ops_for(llama.TINY)
    assert ops.prefill is generate._jit_prefill
    assert ops.init_cache is generate.init_cache
    assert ops.init_pool is paged.init_pool
    assert ops.insert_paged is paged.jit_insert
    assert ops.fork_block is paged.jit_fork_block
    assert ops.prefill_shared is paged.jit_prefill_shared
    assert not ops.refuses and not ops.rows_couple(llama.TINY)
    assert ops.rows_couple(llama.MOE_TINY)          # the capacity path
    assert not model_ops.ops_for(mla_moe.TINY).rows_couple(mla_moe.TINY)
    with pytest.raises(TypeError, match='no serving ops'):
        model_ops.ops_for(object())


def test_the_programs_own_init_matches_its_axes_and_the_harness_tree():
    cfg, fam, _, pcfg = setup()
    own = jax.eval_shape(lambda: mla_moe.init_params(jax.random.PRNGKey(0),
                                                     pcfg))
    axes = mla_moe.param_logical_axes(pcfg)
    theirs = jax.eval_shape(lambda: weights.make_params(cfg, 0))
    assert jax.tree.map(lambda x: x.shape, own) == \
        jax.tree.map(lambda x: x.shape, theirs)
    flat_axes = jax.tree.leaves(axes, is_leaf=lambda a: isinstance(a, tuple))
    assert [len(a) for a in flat_axes] == \
        [x.ndim for x in jax.tree.leaves(own)]
