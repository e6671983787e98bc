"""models/kda.py and the two-cache path of models/mla_moe.py against the
plain reference (benchmarks/reference/kda_mla_moe.py: the recurrence,
token by token) on seeded weights at a tiny size: five layers (KDA,
KDA, KDA, MLA, KDA; the first dense, then 8 experts top-2 plus a shared
one), 2 KDA heads of 16, NoPE MLA without a query down-projection.
Float32 here, so that the comparison is of the mathematics."""
import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import manifest, weights
from skypilot_tpu.models import engine as engine_lib
from skypilot_tpu.models import generate, kda, llama, mla_moe, model_ops
from skypilot_tpu.ops import decode_attention

DATA = os.path.join(os.path.dirname(__file__), 'benchmarks', 'data')
TOL = 2e-4


def setup(seed=5, **changes):
    with open(os.path.join(DATA, 'tiny_kda_mla_moe_config.json')) as f:
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


def _recurrence(q, k, v, g, beta, state):
    def one(s, x):
        q, k, v, g, b = x
        s = s * jnp.exp(g)[..., None]
        u = b[..., None] * (v - jnp.sum(s * k[..., None], -2))
        s = s + k[..., None] * u[..., None, :]
        return s, jnp.sum(s * q[..., None], -2)
    state, o = jax.lax.scan(one, state, tuple(
        jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), state


# 100 and 40 are no multiples of the chunk (64) or of its blocks (16);
# 7 is shorter than a block; 192 is three chunks
@pytest.mark.parametrize('s', [7, 16, 40, 64, 100, 192])
def test_the_chunked_form_is_the_token_recurrence(s):
    b, h, dk = 2, 3, 8
    ks = jax.random.split(jax.random.PRNGKey(s), 6)
    q = jax.random.normal(ks[0], (b, s, h, dk))
    k = kda._l2(jax.random.normal(ks[1], (b, s, h, dk)))
    v = jax.random.normal(ks[2], (b, s, h, dk))
    # decays from none at all to e^-50 a token: nothing may overflow
    g = -jnp.exp(2.0 * jax.random.normal(ks[3], (b, s, h, dk)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, h)))
    state = jax.random.normal(ks[5], (b, h, dk, dk))
    # row 1 is padded on the right: g = beta = 0 there
    real = jnp.arange(s)[None, :] < jnp.asarray([s, max(s - 5, 1)])[:, None]
    g = jnp.where(real[..., None, None], g, 0.0)
    beta = jnp.where(real[..., None], beta, 0.0)
    with jax.default_matmul_precision('highest'):
        want_o, want_s = _recurrence(q, k, v, g, beta, state)
        got_o, got_s = kda.chunked(q, k, v, g, beta, state)
    assert float(jnp.max(jnp.abs(got_o - want_o))) < 5e-4
    assert float(jnp.max(jnp.abs(got_s - want_s))) < 1e-4


@pytest.mark.parametrize('n', [5, 16, 33, 80])
def test_the_mixers_prefill_is_its_own_step_repeated(n):
    """``kda.forward`` over n tokens = ``kda.step`` n times: outputs,
    final state and the convolutions' tails."""
    _, _, params, pcfg = setup()
    layer = jax.tree.map(lambda x: x[0], params['1_kda_moe'])
    h = jax.random.normal(jax.random.PRNGKey(n), (2, n, pcfg.d_model))
    state = jnp.zeros(kda.state_shape(pcfg, 2))
    tail = jnp.zeros(kda.tail_shape(pcfg, 2))
    lens = jnp.asarray([n, n], jnp.int32)
    with jax.default_matmul_precision('highest'):
        y, s_all, t_all = kda.forward(pcfg, h, layer, state, tail, lens)
        ys, live = [], jnp.ones((2,), bool)
        for i in range(n):
            yi, state, tail = kda.step(pcfg, h[:, i], layer, state, tail, live)
            ys.append(yi)
    assert float(jnp.max(jnp.abs(y - jnp.stack(ys, 1)))) < TOL
    assert float(jnp.max(jnp.abs(s_all - state))) < TOL
    assert float(jnp.max(jnp.abs(t_all - tail))) < 1e-5


def test_full_forward_is_the_references():
    cfg, fam, params, pcfg = setup()
    toks = tokens(48)
    want = fam.reference.logits_at(params, toks, np.arange(48), cfg)
    # a cache exactly as wide as the prompt: the fresh (flash) path
    got, _ = mla_moe.forward_cached(params, toks[None],
                                    mla_moe.init_cache(pcfg, 1, 48), pcfg,
                                    all_logits=True)
    assert float(jnp.max(jnp.abs(got[0] - want))) < TOL


def _paged_run(params, pcfg, toks, n_prompt, width=16):
    """Prefill ``toks[:n_prompt]`` densely (padded to ``width``), insert
    it into slot 1 of a pool, then decode the rest one token a step
    with slot 0 inactive: logits at every position."""
    cache = mla_moe.init_cache(pcfg, 1, width)
    padded = np.zeros((1, width), np.int64)
    padded[0, :n_prompt] = toks[:n_prompt]
    logits, cache = mla_moe.forward_cached(
        params, padded, cache, pcfg, jnp.asarray([n_prompt], jnp.int32))
    pool = mla_moe.init_pool(pcfg, 2, 64, 8, 16)
    # slot 1's predecessor left junk behind: none of it may be seen
    pool = dataclasses.replace(pool, state=pool.state + 3.0,
                               conv=pool.conv - 2.0)
    table = np.zeros((1, 4), np.int32)
    table[0, :3] = [5, 2, 7]
    pool = mla_moe.jit_insert(pool, cache, table, np.asarray([1], np.int32))
    out = [logits[0]]
    last = np.zeros((2,), np.int32)
    # skylint: allow-jit(test-only)
    step = jax.jit(lambda p, t, c: mla_moe.forward_paged(
        p, t, c, pcfg, active_rows=jnp.asarray([False, True])))
    junk = (pool.state[:, 0], pool.conv[:, 0])
    for i in range(n_prompt, len(toks)):
        last[1] = toks[i]
        logits, pool, load = step(params, jnp.asarray(last.copy())[:, None],
                                  pool)
        out.append(logits[1])
    # the inactive slot's state is what it was, bit for bit
    assert bool(jnp.all(pool.state[:, 0] == junk[0]))
    assert bool(jnp.all(pool.conv[:, 0] == junk[1]))
    return jnp.stack(out), load


@pytest.mark.parametrize('n_prompt, total', [(11, 24), (16, 30), (3, 12)])
def test_prefill_then_decode_through_state_and_pool_gives_the_references_logits(
        n_prompt, total):
    cfg, fam, params, pcfg = setup()
    toks = tokens(total, seed=3)
    got, load = _paged_run(params, pcfg, toks, n_prompt)
    want = fam.reference.logits_at(params, toks,
                                   np.arange(n_prompt - 1, total), cfg)
    assert float(jnp.max(jnp.abs(got - want))) < TOL
    # one live row, four expert layers, top-2: eight (token, choice) pairs
    assert int(load.sum()) == 8


def _wide_heads():
    """The tiny model with KDA heads of 128: whole lane tiles, which
    ``decode_attention.kda_step`` needs (``kda.step_path``)."""
    with open(os.path.join(DATA, 'tiny_kda_mla_moe_config.json')) as f:
        linear = json.load(f)['linear_attn_config']
    return setup(linear_attn_config=dict(linear, head_dim=128))


@pytest.mark.parametrize('kernel', [False, True], ids=['xla', 'kernel'])
def test_the_decode_step_gives_the_references_logits_under_both_paths(
        kernel, monkeypatch):
    """``test_prefill_then_decode_through_state_and_pool...`` at heads
    of 128, where the rule has a choice: the plain XLA recurrence, and
    the kernels (``kda_step`` and ``mla_decode``) in the interpreter,
    asked for by name. Either way the inactive slot's junk state stays
    bit for bit (``_paged_run``)."""
    cfg, fam, params, pcfg = _wide_heads()
    if kernel:
        monkeypatch.setattr(decode_attention, 'PAGED_INTERPRET', True)
    state = (4, 2) + kda.state_shape(pcfg, 2)[1:]
    assert state[2:] == (2, 128, 128)
    assert kda.step_path(state, jnp.float32) == (
        'kernel' if kernel else 'xla')
    toks = tokens(22, seed=3)
    got, load = _paged_run(params, pcfg, toks, 11)
    want = fam.reference.logits_at(params, toks, np.arange(10, 22), cfg)
    assert float(jnp.max(jnp.abs(got - want))) < TOL
    assert int(load.sum()) == 8


def test_a_padded_group_leaves_each_rows_state_as_its_unpadded_prefill():
    """Rows of 9, 32 and 21 tokens in one group padded to 32: state and
    tails of each row are those of the row prefilled alone at its own
    length (whatever the padding holds)."""
    _, _, params, pcfg = setup()
    lens = [9, 32, 21]
    rows = [tokens(n, seed=20 + i) for i, n in enumerate(lens)]
    padded = np.full((3, 32), 7, np.int64)      # junk, not zeros
    for i, r in enumerate(rows):
        padded[i, :len(r)] = r
    _, group = mla_moe.forward_cached(
        params, padded, mla_moe.init_cache(pcfg, 3, 32), pcfg,
        jnp.asarray(lens, jnp.int32))
    for i, r in enumerate(rows):
        logits, alone = mla_moe.forward_cached(
            params, r[None], mla_moe.init_cache(pcfg, 1, len(r)), pcfg)
        assert float(jnp.max(jnp.abs(group.state[:, i]
                                     - alone.state[:, 0]))) < 1e-5
        assert float(jnp.max(jnp.abs(group.conv[:, i]
                                     - alone.conv[:, 0]))) < 1e-5
        assert int(group.lengths[i]) == len(r)


def test_nope_mla_absorbed_decode_is_its_expanded_form():
    _, _, params, pcfg = setup()
    layer = jax.tree.map(lambda x: x[0], params['3_mla_moe'])
    assert 'wq' in layer and 'wq_a' not in layer and not pcfg.rope
    key = jax.random.PRNGKey(1)
    h = jax.random.normal(key, (3, 1, pcfg.d_model))
    view = jax.random.normal(jax.random.fold_in(key, 1),
                             (3, 24, pcfg.latent_width))
    view = view.at[..., pcfg.latent_dim:].set(0)
    valid = jnp.asarray([24, 7, 1], jnp.int32)
    positions = (valid - 1)[:, None]
    q, _ = mla_moe._q_and_latent(pcfg, h, layer, positions)
    # nothing is rotated: the query is the same at any position
    q0, _ = mla_moe._q_and_latent(pcfg, h, layer, positions * 0)
    assert bool(jnp.all(q == q0))
    expanded = mla_moe._attend_view(pcfg, q, view, layer, positions, valid)
    absorbed = mla_moe._unabsorb(pcfg, mla_moe._absorbed_view(
        pcfg, mla_moe._absorb(pcfg, q[:, 0], layer), view, valid), layer)
    assert float(jnp.max(jnp.abs(expanded[:, 0] - absorbed))) < 1e-5


def test_generate_serves_the_model_through_the_dense_cache():
    """``generate.generate`` (prefill, then the one-token recurrence and
    the absorbed step over the dense cache) picks what the reference's
    full forward picks."""
    cfg, fam, params, pcfg = setup()
    prompt = tokens(12, seed=6)
    out = np.asarray(generate.generate(params, pcfg,
                                       jnp.asarray(prompt)[None], 6))[0]
    seq = np.concatenate([prompt, out])
    logits = fam.reference.logits_at(params, seq[:-1], np.arange(11, 17), cfg)
    best = np.asarray(logits.max(-1))
    got = np.asarray(logits)[np.arange(6), out]
    assert float(np.max(best - got)) < TOL


# -- the precision the configuration states ---------------------------------


def _bf16_setup():
    cfg, fam, _, _ = setup()
    pcfg = fam.program_config(cfg)
    assert pcfg.dtype == jnp.bfloat16
    layer = {k: jnp.zeros(shape, jnp.bfloat16)
             for k, (shape, _, _) in kda.layer_shapes(pcfg).items()}
    return pcfg, layer


def _eqns(jaxpr):
    for e in jaxpr.eqns:
        yield e
        for v in e.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, 'jaxpr', sub)
                if hasattr(inner, 'eqns'):
                    yield from _eqns(inner)


@pytest.mark.parametrize('form', ['step', 'forward'])
def test_state_decay_and_beta_are_float32_under_a_bfloat16_model(form):
    """What ``correct`` cannot see (a state kept in bfloat16 moves the
    served picks less than the bfloat16 activations do: PERF.md, PR 33)
    is held here: with bfloat16 weights and activations both forms take
    and return the state in float32, compute the gates in float32, and
    take every exponential (the decay's, the softplus's) of a float32
    number."""
    pcfg, layer = _bf16_setup()
    b, s = 2, 40
    state = jnp.zeros(kda.state_shape(pcfg, b), jnp.float32)
    tail = jnp.zeros(kda.tail_shape(pcfg, b), jnp.bfloat16)
    if form == 'step':
        fn = lambda h, st, tl: kda.step(                    # noqa: E731
            pcfg, h, layer, st, tl, jnp.ones((b,), bool))
        h = jnp.zeros((b, pcfg.d_model), jnp.bfloat16)
    else:
        fn = lambda h, st, tl: kda.forward(                 # noqa: E731
            pcfg, h, layer, st, tl, jnp.full((b,), s, jnp.int32))
        h = jnp.zeros((b, s, pcfg.d_model), jnp.bfloat16)
    y, s_new, t_new = jax.eval_shape(fn, h, state, tail)
    assert y.dtype == jnp.bfloat16 and t_new.dtype == jnp.bfloat16
    assert s_new.dtype == jnp.float32 and s_new.shape == state.shape
    g, beta = jax.eval_shape(
        lambda h: kda._gates(pcfg, h, layer, jnp.ones(h.shape[:-1], bool)),
        h)
    assert g.dtype == beta.dtype == jnp.float32
    exps = [e for e in _eqns(jax.make_jaxpr(fn)(h, state, tail).jaxpr)
            if e.primitive.name in ('exp', 'exp2', 'log1p', 'logistic')]
    assert exps and all(v.aval.dtype == jnp.float32
                        for e in exps for v in e.invars), [
        (e.primitive.name, e.invars[0].aval.dtype) for e in exps]


@pytest.mark.parametrize('cache', ['dense', 'pool'])
def test_the_caches_keep_the_state_in_float32_whatever_the_models_dtype(
        cache):
    """Prefill, insert and the decode chunk hand the state on in
    float32 (abstractly: shapes and dtypes, nothing runs)."""
    cfg, fam, _, _ = setup()
    pcfg = fam.program_config(cfg)
    params = jax.eval_shape(lambda: weights.make_params(cfg, 0))
    dense = jax.eval_shape(lambda: mla_moe.init_cache(pcfg, 2, 32))
    assert dense.state.dtype == jnp.float32 and dense.k.dtype == jnp.bfloat16
    _, dense = jax.eval_shape(
        lambda p, c: mla_moe.forward_cached(
            p, jnp.zeros((2, 32), jnp.int32), c, pcfg,
            jnp.full((2,), 20, jnp.int32)), params, dense)
    assert dense.state.dtype == jnp.float32
    if cache == 'dense':
        return
    pool = jax.eval_shape(lambda: mla_moe.init_pool(pcfg, 4, 64, 17, 16))
    assert pool.state.dtype == jnp.float32
    pool = jax.eval_shape(mla_moe._insert_impl, pool, dense,
                          jnp.zeros((2, 4), jnp.int32),
                          jnp.zeros((2,), jnp.int32))
    assert pool.state.dtype == jnp.float32
    pool = jax.eval_shape(
        lambda p, c: mla_moe._paged_chunk_impl(
            pcfg, 2, p, c, jnp.zeros((4,), jnp.int32),
            jnp.zeros((4,), jnp.float32), None, None,
            jnp.ones((4,), bool), jax.random.PRNGKey(0))[0], params, pool)
    assert pool.state.dtype == jnp.float32 and pool.conv.dtype == jnp.bfloat16


# -- through the engine ------------------------------------------------------


def _engine(params, pcfg, **kw):
    base = dict(slots=2, max_len=96, kv_blocks=13, kv_block=16,
                prefill_batch=2, chunk_steps=4, prefix_share=False,
                kv_tiers=False, kv_quantize=False)
    return engine_lib.ContinuousEngine(params, pcfg, **dict(base, **kw))


def _picks_are_the_references(fam, params, cfg, row, out):
    seq = np.concatenate([row, out])
    logits = fam.reference.logits_at(
        params, seq[:-1], np.arange(len(row) - 1, len(seq) - 1), cfg)
    best = np.asarray(logits.max(-1))
    got = np.asarray(logits)[np.arange(len(out)), out]
    assert float(np.max(best - got)) < TOL, (best - got)


def test_a_slot_freed_and_readmitted_sees_none_of_its_predecessors_state():
    """Two slots, five requests of unequal length, two at a time in one
    prefill group: every slot is re-used, and every served token is the
    reference's pick for ITS prompt alone."""
    cfg, fam, params, pcfg = setup()
    eng = _engine(params, pcfg)
    try:
        assert eng.pipeline_depth == 1 and not eng.prefix_share
        assert eng._trie is None and eng._kv_tiers is None
        rows = [tokens(n, seed=30 + i)
                for i, n in enumerate([40, 13, 29, 50, 7])]
        futs = [eng.submit(list(map(int, r)), 6) for r in rows]
        outs = [f.result(timeout=300) for f in futs]
        for row, out in zip(rows, outs):
            _picks_are_the_references(fam, params, cfg, row, out)
        st = eng.stats()
        assert st['kv_bytes_per_token'] == 1 * (32 + 8) * 4  # float32 here
        # four KDA layers: a [2, 16, 16] float32 state, 3 x 96 tails
        assert st['state_bytes_per_slot'] == 4 * (2 * 16 * 16 * 4
                                                  + 3 * 96 * 4)
        assert st['decode_attention'] == 'gather'
        # heads of 16 on a CPU: the plain recurrence
        assert st['kda_step'] == 'xla'
        assert st['moe_tokens_routed'] % 8 == 0 and st['moe_tokens_routed']
    finally:
        eng.stop()


@pytest.mark.parametrize('piece', [0, 32], ids=['paged_chunk',
                                                'paged_chunk_n'])
def test_the_engine_serves_through_the_kernel_and_says_so(piece,
                                                          monkeypatch):
    """Both decode programs (the whole chunk, and with pieces on the
    chunk that stops at its first row to finish) with ``kda_step`` in
    them (the interpreter, asked for by name; heads of 128): slots are
    freed and re-used, one stays empty at times, and every served token
    is the reference's pick; ``stats()`` names the path."""
    cfg, fam, params, pcfg = _wide_heads()
    monkeypatch.setattr(decode_attention, 'PAGED_INTERPRET', True)
    programs = (mla_moe.jit_paged_chunk, mla_moe.jit_paged_chunk_n)
    # the path is chosen when a program is traced
    for p in programs:
        p.clear_cache()
    eng = _engine(params, pcfg, prefill_chunk=piece)
    try:
        assert bool(eng._trim_chunks) == bool(piece)
        rows = [tokens(n, seed=40 + i) for i, n in enumerate([40, 13, 29])]
        futs = [eng.submit(list(map(int, r)), k)
                for r, k in zip(rows, [6, 9, 5])]
        outs = [f.result(timeout=600) for f in futs]
        for row, out in zip(rows, outs):
            _picks_are_the_references(fam, params, cfg, row, out)
        st = eng.stats()
        assert st['kda_step'] == 'kernel'
        assert st['decode_attention'] == 'mla_kernel'
    finally:
        eng.stop()
        for p in programs:
            p.clear_cache()


@pytest.mark.parametrize('piece', [16, 24, 64])
def test_a_long_prompt_prefilled_in_pieces_is_the_references(piece):
    """The chunked long prefill (``prefill_chunk``): a prompt longer
    than the piece advances a piece an engine iteration over a scratch
    row whose state, tails and latent rows each piece continues; shorter
    prompts keep the group prefill; a request that ends at its first
    token leaves junk in a free slot that the next admission replaces.
    24 is no multiple of the mixer's blocks of 16."""
    cfg, fam, params, pcfg = setup()
    eng = _engine(params, pcfg, prefill_chunk=piece)
    try:
        assert eng.prefill_chunk == piece
        lens = [70, 13, 49, 80, 7, 33]
        news = [6, 6, 1, 5, 6, 6]
        rows = [tokens(n, seed=60 + i) for i, n in enumerate(lens)]
        futs = [eng.submit(list(map(int, r)), k)
                for r, k in zip(rows, news)]
        outs = [f.result(timeout=300) for f in futs]
        for row, out, k in zip(rows, outs, news):
            assert len(out) == k
            _picks_are_the_references(fam, params, cfg, row, out)
        st = eng.stats()
        want = sum(-(-n // piece) for n in lens if n > piece)
        assert st['prefill_chunks'] == want
        assert st['prefill_tokens'] == sum(lens)
    finally:
        eng.stop()


@pytest.mark.parametrize('max_new, steps', [(6, [4, 1, 1]), (9, [4, 4, 1]),
                                            (3, [2, 1]), (11, [4, 4, 2, 1])])
def test_a_chunk_ends_with_its_first_row_to_finish(max_new, steps):
    """Where pieces go between the chunks: a lone row owed 5 tokens
    after its first takes a chunk of 4 steps and one of 1 (then one
    junk step: dispatch and retirement alternate), not three of 4; the
    tokens are the reference's all the same."""
    cfg, fam, params, pcfg = setup()
    eng = _engine(params, pcfg, prefill_chunk=32)
    try:
        assert eng._trim_chunks
        seen = []
        inner = eng._steps_to_first_finish
        eng._steps_to_first_finish = lambda reqs: (
            seen.append(inner(reqs)) or seen[-1])
        row = tokens(21, seed=90)
        out = eng.submit(list(map(int, row)), max_new).result(timeout=300)
        assert len(out) == max_new
        _picks_are_the_references(fam, params, cfg, row, out)
        assert seen == steps
        assert eng.stats()['pipeline']['decode_steps'] == sum(steps)
    finally:
        eng.stop()


def test_without_pieces_a_chunk_stays_whole():
    _, _, params, pcfg = setup()
    eng = _engine(params, pcfg, prefill_chunk=0)
    try:
        assert not eng._trim_chunks
        out = eng.submit(list(map(int, tokens(21, seed=90))), 6).result(
            timeout=300)
        assert len(out) == 6
        st = eng.stats()['pipeline']
        assert st['decode_steps'] == 4 * st['dispatches']
    finally:
        eng.stop()


def test_a_piece_follows_at_least_chunk_steps_of_the_live_rows():
    """A row is live while two long prompts go in pieces of 16: between
    two pieces dispatched beside a live row lie at least ``chunk_steps``
    decode steps, however the chunks were cut; every request's tokens
    are the reference's."""
    cfg, fam, params, pcfg = setup()
    eng = _engine(params, pcfg, prefill_chunk=16, slots=3, kv_blocks=19)
    try:
        log = []
        steps_of, piece = eng._steps_to_first_finish, eng._prefill_one_chunk

        def steps(reqs):
            log.append(('steps', steps_of(reqs)))
            return log[-1][1]

        def one_piece(*a):
            log.append(('piece', any(r is not None
                                     for r in eng._slot_req)))
            return piece(*a)
        eng._steps_to_first_finish, eng._prefill_one_chunk = steps, one_piece
        rows = [tokens(n, seed=70 + i) for i, n in enumerate([20, 70, 66])]
        news = [30, 7, 5]
        first = eng.submit(list(map(int, rows[0])), news[0])
        while not eng.stats()['active_slots']:
            time.sleep(0.01)
        futs = [first] + [eng.submit(list(map(int, r)), k)
                          for r, k in zip(rows[1:], news[1:])]
        outs = [f.result(timeout=300) for f in futs]
        for row, out in zip(rows, outs):
            _picks_are_the_references(fam, params, cfg, row, out)
        since, beside = eng.chunk_steps, 0
        for what, value in log:
            if what == 'steps':
                since += value
            else:
                if value:
                    assert since >= eng.chunk_steps, log
                    beside += 1
                since = 0
        assert beside >= 4, log
    finally:
        eng.stop()


def test_the_familys_own_piece_is_the_default_and_zero_turns_it_off(
        monkeypatch):
    _, _, params, pcfg = setup()
    monkeypatch.delenv('SKYTPU_LLM_PREFILL_CHUNK', raising=False)
    for kwargs, want in [({}, 512), (dict(prefill_chunk=0), 0),
                         (dict(prefill_chunk=32), 32)]:
        eng = _engine(params, pcfg, **kwargs)
        try:
            assert eng.prefill_chunk == want
        finally:
            eng.stop()
    monkeypatch.setenv('SKYTPU_LLM_PREFILL_CHUNK', '48')
    eng = _engine(params, pcfg)
    try:
        assert eng.prefill_chunk == 48
    finally:
        eng.stop()


@pytest.mark.parametrize('kwargs, feature', [
    (dict(prefix_share=True), 'prefix sharing'),
    (dict(kv_quantize=True), 'kv_quantize'),
    (dict(kv_tiers=True), 'kv_tiers'),
    (dict(draft_params={}, draft_cfg=llama.TINY), 'speculative decoding')],
    ids=lambda v: v if isinstance(v, str) else '')
def test_what_a_state_beside_the_blocks_rules_out_is_refused_by_name(
        kwargs, feature):
    _, _, params, pcfg = setup()
    with pytest.raises(ValueError, match=feature):
        _engine(params, pcfg, **kwargs)


def test_handoff_is_refused_and_sharing_defaults_off_for_a_model_with_state():
    _, _, params, pcfg = setup()
    eng = engine_lib.ContinuousEngine(params, pcfg, slots=2, max_len=64,
                                      kv_blocks=9)
    try:
        assert not eng.prefix_share and eng._kv_tiers is None
        with pytest.raises(ValueError, match='KV handoff'):
            eng.submit_prefill([1, 2, 3], 4)
        with pytest.raises(ValueError, match='KV handoff'):
            eng.submit_import([1, 2, 3], 4, 5)
    finally:
        eng.stop()


def test_the_table_has_the_familys_row_and_its_reasons():
    _, _, _, pcfg = setup()
    ops = model_ops.ops_for(pcfg)
    assert ops.name == 'kda_mla_moe'
    assert ops.insert_paged is mla_moe.jit_insert
    assert ops.paged_chunk is mla_moe.jit_paged_chunk
    assert ops.state_bytes_per_slot(pcfg) == pcfg.state_bytes_per_slot > 0
    assert model_ops.ops_for(mla_moe.TINY).state_bytes_per_slot(
        mla_moe.TINY) == 0
    assert 'state' in ops.refuses['prefix sharing']
    # what the latent family refuses, but for the chunked long prefill
    # (its pieces carry the state on), and sharing besides
    assert set(ops.refuses) == (
        set(model_ops.ops_for(mla_moe.TINY).refuses) - {'prefill_chunk'}
        | {'prefix sharing'})
    assert ops.prefill_chunk(pcfg) == 512 == 8 * kda.CHUNK
    assert model_ops.ops_for(mla_moe.TINY).prefill_chunk(mla_moe.TINY) == 0
    assert model_ops.ops_for(llama.TINY).prefill_chunk(llama.TINY) == 0
    # the family's own rule, under the family's own stats() key; the
    # other families report none
    shapes = jax.eval_shape(lambda: mla_moe.init_pool(pcfg, 2, 64, 9, 16))
    assert ops.step_paths(shapes) == {'kda_step': 'xla'}
    assert model_ops.ops_for(mla_moe.TINY).step_paths(None) == {}
    assert model_ops.ops_for(llama.TINY).step_paths(None) == {}
    with open(engine_lib.__file__) as f:
        src = f.read()
    assert 'kda' not in src.lower() and 'Kimi' not in src


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
    # a model of one kind keeps the two stacks it always had
    assert set(mla_moe.init_params(jax.random.PRNGKey(0), mla_moe.TINY)) == {
        'embed', 'final_norm', 'lm_head', 'dense', 'moe'}


def test_the_programs_two_half_shares_add_up_to_the_references_layer():
    """``moe.dropfree_mlp`` told it holds experts [0, 4) and, on the
    other chip, [4, 8), the shared expert counted once: the two parts
    add up to the uncut reference's expert layer."""
    from skypilot_tpu.models import moe
    cfg, fam, params, pcfg = setup()
    w = jax.tree.map(lambda x: x[0], params['1_kda_moe'])
    x = jax.random.normal(jax.random.PRNGKey(0), (6, pcfg.d_model))
    ref = fam.reference
    want = ref.experts(x, w, dict(fam.static(cfg)), None)
    shared = ref.shared.swiglu(x, w['ws_gate'], w['ws_up'], w['ws_down'],
                               None)
    parts = []
    for lo, hi in ((0, 4), (4, 8)):
        share = dict(w, **{k: w[k][lo:hi]
                           for k in ('we_gate', 'we_up', 'we_down')})
        y, load = moe.dropfree_mlp(x, share, pcfg.expert_top_k,
                                   pcfg.routed_scale, pcfg.norm_topk_prob,
                                   (lo, hi))
        parts.append(y - shared)
        assert int(load.sum()) == 6 * 2     # routing is over all 8
    assert float(jnp.max(jnp.abs(sum(parts) + shared - want))) < 1e-4
