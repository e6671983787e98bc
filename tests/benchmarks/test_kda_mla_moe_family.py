"""The ``kda_mla_moe`` family (Kimi-Linear-48B-A3B) in the benchmark:
its configuration's cut, its leaves, its counts, and its reference (the
token recurrence) against what the family's equations say, at a tiny
size on the CPU. The program against this reference is
``tests/test_kda.py``'s."""
import hashlib
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import manifest, weights

DATA = os.path.join(os.path.dirname(__file__), 'data')
CELL = 'kimi-linear-docs-steady'


def _cfg(**changes):
    with open(os.path.join(DATA, 'tiny_kda_mla_moe_config.json')) as f:
        cfg = json.load(f)
    cfg.update(changes)
    return cfg


def _f32(tree):
    return jax.tree.map(lambda x: x.astype(jnp.float32), tree)


def test_the_cells_configuration_is_the_published_one_cut_as_stated():
    cfg = manifest.config_of(manifest.cell(CELL))
    with open('/opt/skills/guides/model-configs/architectures.jsonl') as f:
        rows = [json.loads(line) for line in f]
    (row,) = [r for r in rows if r['name'] == 'Kimi-Linear-48B-A3B-Instruct']
    assert cfg['source'] == row['source_url']
    cut = {'num_hidden_layers', 'linear_attn_config', 'num_experts'}
    assert set(cfg['reduced']) == cut
    for key, val in row['config'].items():
        if key in cut:
            assert cfg['published'][key] == val, key
        else:
            assert cfg[key] == val, key
    lin, pub = cfg['linear_attn_config'], row['config']['linear_attn_config']
    assert {k: lin[k] for k in ('head_dim', 'num_heads',
                                'short_conv_kernel_size')} == \
        {k: pub[k] for k in ('head_dim', 'num_heads',
                             'short_conv_kernel_size')}
    # one leading dense layer, then one whole 3 KDA : 1 MLA period
    assert (cfg['num_hidden_layers'], lin['kda_layers'],
            lin['full_attn_layers']) == (5, [1, 2, 3, 5], [4])
    assert pub['kda_layers'][:4] == [1, 2, 3, 5]
    assert pub['full_attn_layers'][0] == 4
    assert cfg['num_experts'] == 128 and cfg['expert_range'] == [0, 128]
    assert 'v5e-16' in cfg['stated']['deployment']
    assert set(cfg['assumed']) >= {'kda', 'attention', 'experts', 'weights'}
    fam = manifest.family(cfg)
    fam.check(cfg)
    held = sum(math.prod(s) for s, _, _ in fam.leaves(cfg).values())
    assert held == cfg['parameters_held'] == 4_660_423_552   # 9.32 GB
    assert int(fam.param_count(cfg)) == cfg['parameters_active']
    assert fam.state_bytes(cfg) == 4 * (32 * 128 * 128 * 4
                                        + 3 * 12288 * 2) == 8_683_520
    pcfg = fam.program_config(cfg)
    assert pcfg.kinds == ('kda', 'kda', 'kda', 'mla', 'kda')
    assert pcfg.num_experts == 256 and pcfg.held == (0, 128)
    assert pcfg.kv_bytes_per_token == 1152
    assert pcfg.state_bytes_per_slot == fam.state_bytes(cfg)
    assert pcfg.q_lora_rank is None and not pcfg.rope


@pytest.mark.parametrize('changes, word', [
    (dict(moe_router_activation_func='softmax'), 'sigmoid'),
    (dict(mla_use_nope=False), 'mla_use_nope'),
    (dict(q_lora_rank=24), 'q_lora_rank'),
    (dict(linear_attn_config=dict(_cfg()['linear_attn_config'],
                                  full_attn_layers=[3, 4])), 'once'),
    (dict(expert_range=[0, 4]), 'expert_range')],
    ids=lambda v: v if isinstance(v, str) else '')
def test_a_file_that_breaks_the_familys_rules_is_refused(changes, word):
    with pytest.raises(ValueError, match=word):
        manifest.family(_cfg()).check(_cfg(**changes))


# sha256 over the leaves (``jax.tree.leaves`` order, bfloat16 bits) of
# ``weights.make_params(tiny_kda_mla_moe_config.json, 7)`` as this PR
# first drew them: the leaves' order fixes each leaf's ``fold_in`` index.
WEIGHTS_SHA_SEED_7 = (
    '5a1c6ad333c5f2be423045b78362d8e26bb191602c32fdb136d372a04e73a73b')


def test_the_leaves_nest_into_the_programs_tree_in_a_fixed_order():
    cfg = _cfg()
    fam = manifest.family(cfg)
    names = list(fam.leaves(cfg))
    assert names[0] == 'embed' and names[-2:] == ['final_norm', 'lm_head']
    runs = ['0_kda_dense', '1_kda_moe', '3_mla_moe', '4_kda_moe']
    assert [r for r, *_ in fam._runs(cfg)] == runs
    assert [names.index(f'{r}/attn_norm') for r in runs] == sorted(
        names.index(f'{r}/attn_norm') for r in runs)
    assert names.index('1_kda_moe/kda_wqkv') < names.index(
        '1_kda_moe/router') < names.index('3_mla_moe/wq')
    assert fam.leaves(cfg)['1_kda_moe/we_gate'][0] == (2, 8, 64, 32)
    params = weights.make_params(cfg, 7)
    assert set(params) == set(runs) | {'embed', 'final_norm', 'lm_head'}
    # dt_bias lies where a trained one does; A_log about 0
    dt = np.asarray(params['1_kda_moe']['kda_dt_bias'], np.float32)
    assert -3.0 < dt.mean() < -2.0 and 0.3 < dt.std() < 0.7
    h = hashlib.sha256()
    for x in jax.tree.leaves(params):
        h.update(np.asarray(x).view(np.uint16).tobytes())
    assert h.hexdigest() == WEIGHTS_SHA_SEED_7


def test_the_decay_stays_away_from_0_and_1():
    """The seeded draws of ``A_log`` and ``dt_bias`` (the
    configuration's ``assumed.weights``): alpha = exp(g) over a
    sequence has its median near 0.9 and all but a sliver of it inside
    (0.05, 0.999)."""
    cfg = _cfg()
    fam = manifest.family(cfg)
    params = _f32(weights.make_params(cfg, 3))
    w = jax.tree.map(lambda x: x[0], params['1_kda_moe'])
    x = jax.random.normal(jax.random.PRNGKey(0), (256, 64))
    f = jnp.einsum('tr,rhk->thk', x @ w['kda_wf_a'], w['kda_wf_b'])
    alpha = np.asarray(jnp.exp(-jnp.exp(w['kda_a_log'])[:, None]
                               * jax.nn.softplus(f + w['kda_dt_bias'])))
    assert 0.85 < np.median(alpha) < 0.97
    assert np.mean((alpha > 0.05) & (alpha < 0.999)) > 0.99


def test_counts_are_the_docstrings_formulas_at_the_tiny_size():
    cfg = _cfg()
    fam = manifest.family(cfg)
    d, kh, dk, taps, r = 64, 2, 16, 4, 16
    kda = (d * 3 * kh * dk + taps * 3 * kh * dk + 2 * (d * r + r * kh * dk)
           + d * kh + kh * dk * d)
    mla = d * 4 * 24 + d * 40 + 32 * 4 * 32 + 4 * 16 * d
    expert = 3 * d * 32
    outside = (4 * kda + mla + 3 * d * 128 + 4 * (d * 8 + expert) + d * 512)
    assert fam.param_count(cfg) == outside + 4 * 2 * expert
    # half the experts held: half of a token's routed experts are here
    half = dict(cfg, num_experts=4, expert_range=[4, 8],
                published={'num_experts': 8})
    fam.check(half)
    assert fam.param_count(half) == outside + 4 * 1 * expert
    rec = 7.0 * kh * dk * dk * 4
    assert fam.forward_flops(cfg, 10, 300) == pytest.approx(
        (2 * fam.param_count(cfg) + rec) * 10 + 2 * 4 * (24 + 16) * 300)
    flops, nbytes = fam.decode_step(cfg, 5, 300)
    touched = fam.experts_touched(cfg, 5)
    assert touched == pytest.approx(8 * (1 - 0.75 ** 5))
    assert fam.experts_touched(half, 5) == pytest.approx(touched / 2)
    norms = (2 * 5 + 1) * d + 32 + 4 * (dk + kh + kh * dk)
    state = 4 * (kh * dk * dk * 4 + 3 * 3 * kh * dk * 2)
    assert fam.state_bytes(cfg) == state
    assert nbytes == pytest.approx(
        2 * (outside + norms + 4 * touched * expert) + 300 * 40 * 2
        + 2 * 5 * state)
    assert flops == pytest.approx(
        2.0 * fam.param_count(cfg) * 5 + 2.0 * 4 * (40 + 32) * 300 + rec * 5)


def test_the_cells_decode_step_reads_what_the_issue_counted():
    """At the cell's size, ~30 live rows at ~1.7k positions: the held
    experts touched ~4.5 GB, every other weight 1.32 GB (the head alone
    2304 x 163840 x 2 = 0.76 GB), the state 0.52 GB read and written,
    one layer of latent rows."""
    cfg = manifest.config_of(manifest.cell(CELL))
    fam = manifest.family(cfg)
    _, nbytes = fam.decode_step(cfg, 30, 30 * 1700)
    experts = 4 * fam.experts_touched(cfg, 30) * 3 * 2304 * 1024 * 2
    assert 0.60 < fam.experts_touched(cfg, 30) / 128 < 0.62
    assert 4.4e9 < experts < 4.6e9
    state = 2 * 30 * fam.state_bytes(cfg)
    assert 0.50e9 < state < 0.54e9
    assert nbytes - experts - state - 30 * 1700 * 1152 == pytest.approx(
        1.318e9, rel=0.01)


def test_the_references_kda_is_the_recurrence_written_out_by_hand():
    """One KDA mixer on 9 tokens against numpy loops over tokens, heads
    and channels' taps."""
    cfg = _cfg()
    fam = manifest.family(cfg)
    ref = fam.reference
    rcfg = dict(fam.static(cfg))
    params = _f32(weights.make_params(cfg, 11))
    w = {k: np.asarray(v[0], np.float64)
         for k, v in params['1_kda_moe'].items()}
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(4), (9, 64)),
                   np.float64)
    got = ref.kda(jnp.asarray(x, jnp.float32), jax.tree.map(
        lambda a: a[0], params['1_kda_moe']), rcfg, None)
    t, kh, dk = 9, 2, 16
    pre = x @ w['kda_wqkv']
    conv = np.zeros_like(pre)
    for i in range(t):
        for tap in range(4):
            j = i - 3 + tap
            if j >= 0:
                conv[i] += w['kda_conv'][tap] * pre[j]
    act = conv / (1 + np.exp(-conv))
    q, k, v = (a.reshape(t, kh, dk) for a in np.split(act, 3, -1))
    q = q / np.sqrt((q * q).sum(-1, keepdims=True) + 1e-6) * dk ** -0.5
    k = k / np.sqrt((k * k).sum(-1, keepdims=True) + 1e-6)
    f = np.einsum('tr,rhk->thk', x @ w['kda_wf_a'], w['kda_wf_b'])
    g = -np.exp(w['kda_a_log'])[:, None] * np.log1p(
        np.exp(f + w['kda_dt_bias']))
    beta = 1 / (1 + np.exp(-(x @ w['kda_wbeta'])))
    s = np.zeros((kh, dk, dk))
    o = np.zeros((t, kh, dk))
    for i in range(t):
        for h in range(kh):
            s[h] = np.exp(g[i, h])[:, None] * s[h]
            u = beta[i, h] * (v[i, h] - s[h].T @ k[i, h])
            s[h] = s[h] + np.outer(k[i, h], u)
            o[i, h] = s[h].T @ q[i, h]
    gate = np.einsum('tr,rhk->thk', x @ w['kda_wg_a'], w['kda_wg_b'])
    o = (o / np.sqrt((o * o).mean(-1, keepdims=True) + cfg['rms_norm_eps'])
         * w['kda_o_norm'] / (1 + np.exp(-gate)))
    want = np.einsum('thk,hkd->td', o, w['kda_wo'])
    assert float(np.max(np.abs(np.asarray(got) - want))) < 2e-5


def test_the_two_half_shares_of_the_experts_add_up_to_the_uncut_layer():
    """Experts [0, E/2) on one chip, [E/2, E) on the other, the shared
    expert counted once: the parts add up to the layer with every
    expert held; the held weights are exactly the range's slice."""
    cfg = _cfg()
    fam = manifest.family(cfg)
    ref = fam.reference
    params = _f32(weights.make_params(cfg, 2))
    w = jax.tree.map(lambda x: x[0], params['1_kda_moe'])
    x = jax.random.normal(jax.random.PRNGKey(0), (6, 64))
    rcfg = dict(fam.static(cfg))
    whole = ref.experts(x, w, rcfg, None)
    shared = ref.shared.swiglu(x, w['ws_gate'], w['ws_up'], w['ws_down'],
                               None)
    parts = []
    for lo, hi in ((0, 4), (4, 8)):
        share = dict(w, **{k: w[k][lo:hi]
                           for k in ('we_gate', 'we_up', 'we_down')})
        parts.append(ref.experts(x, share, dict(rcfg, expert_range=(lo, hi)),
                                 None) - shared)
    assert float(jnp.max(jnp.abs(sum(parts) + shared - whole))) < 1e-5
    assert float(jnp.max(jnp.abs(parts[0]))) > 1e-3
    # the control is the same mathematics, coarser
    low = ref.experts(x, w, rcfg, 'int8')
    assert 1e-4 < float(jnp.max(jnp.abs(low - whole))) < 0.5


@pytest.mark.parametrize('seed', [5, 6])
def test_the_int8_control_moves_the_references_logits(seed):
    """The control the cell's limit is set against (the one control:
    a state kept in bfloat16 moves the picks less than the program's
    own bfloat16 activations, so ``tests/test_kda.py`` holds the
    program's state to float32 and the reference carries no such
    control)."""
    cfg = _cfg()
    fam = manifest.family(cfg)
    params = weights.make_params(cfg, seed)
    toks = np.random.default_rng(seed).integers(0, 512, size=(40,))
    rows = np.arange(40)
    want = fam.reference.logits_at(params, toks, rows, cfg)
    got = fam.reference.logits_at(params, toks, rows, cfg, 'int8')
    err = float(jnp.max(jnp.abs(got - want)))
    assert 1e-2 < err < 10.0
    # padding on the right changes nothing before it
    padded = np.concatenate([toks, np.zeros((24,), toks.dtype)])
    again = fam.reference.logits_at(params, padded, rows, cfg)
    assert float(jnp.max(jnp.abs(again - want))) < 1e-5


def test_the_new_metrics_have_files_and_the_cell_reports_them():
    bench = manifest.benchmark()
    names = {m['name'] for m in manifest.per_layer_for(CELL, bench)}
    assert {'step.kda_share.tpot', 'step.kda_share.ttft',
            f'step.moe_share.tpot.{CELL}', 'step.mla_share.tpot',
            'mla_decode_roofline.tpot', 'kv.state_bytes_per_slot.tpot',
            'kv.bytes_per_token.tpot', 'decode_step_roofline.tpot',
            'serve.mfu.tpot', 'moe.load_max_over_mean.tpot'} <= names
    assert 'step.moe_share.tpot' not in names      # Xing's 192 rows
    mix = manifest.traffic_of(manifest.cell(CELL))
    eng = mix['engine']
    # 48 slots x 8 a token: the sorted rows of a decode step
    spec = manifest.metric_file(f'step.moe_share.tpot.{CELL}')
    assert f'[{eng["slots"] * 8},' in spec['args']['match'].replace('\\', '')
    assert eng['prefix_share'] is False and mix['loop'] == 'open'
    assert (eng['kv_blocks'] - 1) * eng['kv_block'] == \
        eng['slots'] * eng['max_len']


def test_the_state_reader_reads_the_key_or_nothing():
    import types
    from benchmarks.readers import engine_stat
    ctx = types.SimpleNamespace(stats1={'state_bytes_per_slot': 8683520})
    assert engine_stat.read(ctx, 'state_bytes_per_slot') == 8683520.0
    # a program without the key (the parent of this PR): nothing, no error
    assert engine_stat.read(types.SimpleNamespace(stats1={'slots': 4}),
                            'state_bytes_per_slot') is None
    assert engine_stat.read(types.SimpleNamespace(stats1=None), 'x') is None


def _tiny_run(trace, hook=None):
    import copy
    from benchmarks import run
    mix = {"kind": "serve", "loop": "open", "rate_rps": 10.0, "ramp_s": 0.5,
           "cooldown_s": 0.3,
           "prompt": {"dist": "lognormal", "median": 30, "sigma": 0.5,
                      "min": 12, "max": 60},
           "answer": {"dist": "uniform", "min": 4, "max": 10},
           "engine": {"slots": 4, "max_len": 128, "kv_layout": "paged",
                      "kv_block": 16, "kv_blocks": 33, "prefill_batch": 2,
                      "chunk_steps": 4, "prefix_share": False,
                      "kv_tiers": False, "tp": 1}}
    # The bfloat16 program against the float32 recurrence at a width of
    # 64, every expert selected: with 2 of 8 a near-tie of the router
    # flips an expert in bfloat16 and the state carries the flip on for
    # tens of tokens (logits off by ~2; the chip's limit has room for
    # that, PERF.md), which is not what this test is about.
    return run.run_cell(CELL, 2**31 + 9, 1.5, trace, require_chip=False,
                        bench=copy.deepcopy(manifest.benchmark()),
                        cfg=_cfg(num_experts_per_token=8), mix=mix,
                        limits={'gap_mean': 0.01, 'missing': 0}, hook=hook)


@pytest.mark.parametrize('piece', [None, 16], ids=['whole', 'pieces'])
@pytest.mark.parametrize('trace', [False, True], ids=['plain', 'traced'])
def test_the_cell_runs_through_the_harness_on_the_cpu(trace, piece,
                                                      monkeypatch):
    """``pieces``: the family's default piece brought down to the tiny
    prompts (17 to 60 tokens go in pieces of 16, as the cell's 513 to
    3,840 go in pieces of 512): the harness's warm-up has to reach the
    piece's program and the scratch row's insert, or they compile in
    the window."""
    from skypilot_tpu.models import model_ops
    seen = {}
    if piece:
        monkeypatch.setattr(model_ops, 'KDA_PREFILL_CHUNK', piece)

    def hook(run_, stage):
        if stage == 'warmed':
            seen['engine'] = run_.engine
            seen['warm_pieces'] = run_.engine.stats()['prefill_chunks']

    res = _tiny_run(trace, hook)
    assert res['correct'] is True, res['compared']
    assert res['attempted'] > 0 and res['failed'] == 0
    assert seen['engine'].prefill_chunk == (piece or 512)
    assert (seen['warm_pieces'] > 0) == bool(piece)
    names = set(res['metrics'])
    if not trace:
        assert {'setup_s', 'tpot_p90_ms'} <= names
        return
    assert res['metrics']['kv.state_bytes_per_slot.tpot']['value'] == \
        4 * (2 * 16 * 16 * 4 + 3 * 96 * 2)
    assert res['metrics']['kv.bytes_per_token.tpot']['value'] == 40 * 2
    assert res['metrics']['compile.in_window.tpot']['value'] == 0
    assert res['metrics']['moe.load_max_over_mean.tpot']['value'] >= 1.0
    # no chip here: nothing read from a device trace, no share of a peak
    assert not any(n.startswith(('step.', 'device.idle', 'serve.mfu',
                                 'decode_step_roofline', 'mla_decode'))
                   for n in names)


def test_a_state_left_behind_by_a_slots_predecessor_is_not_correct():
    """The fault the state brings: the insert drops the rows' state
    (each admission decodes on from whatever its slot held). Served
    tokens then part from the reference's and the cell says so."""
    from skypilot_tpu.models import mla_moe

    def hook(run_, stage):
        if stage != 'built':
            return
        ops = run_.engine._ops
        inner = ops.insert_paged

        def lossy(pool, cache_n, tables, slots):
            zero = jax.tree.map(jnp.zeros_like, (cache_n.state, cache_n.conv))
            return inner(pool, mla_moe.StateKVCache(
                k=cache_n.k, v=None, lengths=cache_n.lengths,
                state=zero[0] + 1.0, conv=zero[1]), tables, slots)
        import dataclasses
        run_.engine._ops = dataclasses.replace(ops, insert_paged=lossy)

    res = _tiny_run(False, hook)
    assert res['correct'] is False
    value, limit = res['compared']['gap_mean']
    assert value > 5 * limit
