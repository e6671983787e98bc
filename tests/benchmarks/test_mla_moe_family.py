"""The ``mla_moe`` family's own files: its tiny configuration runs
through the harness on the CPU and an altered token is not correct; its
counts are what its docstring says at a shape small enough to work out
by hand; its readers read a recorded shape of a run and give nothing
where there is nothing to read."""
import hashlib
import json
import math
import os
import types

import jax
import numpy as np
import pytest
import test_run_cpu     # beside this file: the tiny mixes and the fault

from benchmarks import manifest, run, weights
from benchmarks import trace as tr
from benchmarks.readers import engine_keys, mla_decode as mla_reader
from benchmarks.roofline import mla_decode as mla_roofline

_load = test_run_cpu._load
# bfloat16 at 64 wide with 8 experts: a near-tie of the router flips an
# expert now and then (seeds read 0 to 0.013); an altered token reads ~1
LIMITS = {'gap_mean': 0.05, 'missing': 0}


def _cfg(**changes):
    return dict(_load('tiny_mla_moe_config.json'), **changes)


def _run(hook=None):
    return run.run_cell('sessions-prefix', 2**31 + 21, 1.5, False,
                        require_chip=False, cfg=_cfg(),
                        mix=_load('tiny_sessions.json'), limits=LIMITS,
                        hook=hook)


def test_the_familys_tiny_cell_runs_through_the_harness_and_is_correct():
    res = _run()
    assert res['correct'] is True, res['compared']
    assert res['attempted'] > 0 and res['failed'] == 0
    assert {'setup_s', 'tpot_p90_ms'} <= set(res['metrics'])


def test_an_altered_token_is_not_correct():
    res = _run(hook=test_run_cpu._alter_tokens)
    assert res['correct'] is False
    value, limit = res['compared']['gap_mean']
    # every third chunk's tokens are others: how many of the sampled
    # requests' tokens those are depends on the run's timing
    assert value > 3 * limit, value


def test_the_cells_configuration_is_the_published_one_but_for_its_depth():
    (entry,) = [c for c in manifest.benchmark()['configs']
                if c['name'] == 'xing4.0-29b-a4b']
    with open(os.path.join(manifest.ROOT, entry['file'])) as f:
        cfg = json.load(f)
    assert cfg['reduced'] == ['num_hidden_layers', 'first_k_dense_replace']
    assert cfg['published'] == {'num_hidden_layers': 40,
                                'first_k_dense_replace': 2}
    assert (cfg['num_hidden_layers'], cfg['first_k_dense_replace']) == (6, 1)
    widths = {'hidden_size': 3584, 'moe_intermediate' '_size': 1024,
              'n_routed_experts': 64, 'num_experts_per_tok': 4,
              'n_shared_experts': 1, 'kv_lora_rank': 512, 'q_lora_rank': 768,
              'qk_nope_head_dim': 128, 'qk_rope_head_dim': 64,
              'v_head_dim': 128, 'num_attention_heads': 32, 'hc_mult': 4,
              'vocab_size': 131072, 'hc_sinkhorn_iters': 20}
    assert {k: cfg[k] for k in widths} == widths
    assert 'expert_range' not in cfg            # every expert is held
    assert set(cfg['assumed']) >= {'residual_stream', 'attention', 'experts'}
    assert 'v5e-8 pipeline' in cfg['stated']['deployment']
    fam = manifest.family(cfg)
    held = sum(math.prod(s) for s, _, _ in fam.leaves(cfg).values())
    assert held == cfg['parameters_held'] == 4_792_669_828
    assert fam.param_count(cfg) == cfg['parameters_active'] < held / 4


# sha256 over the leaves (``jax.tree.leaves`` order, bfloat16 bits) of
# ``weights.make_params(tiny_mla_moe_config.json, seed)`` as this PR
# first drew them: the leaves' order fixes each leaf's ``fold_in`` index.
def test_the_leaves_nest_into_the_programs_tree_in_a_fixed_order():
    cfg = _cfg()
    fam = manifest.family(cfg)
    names = list(fam.leaves(cfg))
    assert names[0] == 'embed' and names[-2:] == ['final_norm', 'lm_head']
    assert names.index('dense/wq_a') < names.index('moe/wq_a')
    tree = fam.tree({n: n for n in names})
    assert set(tree) == {'embed', 'dense', 'moe', 'final_norm', 'lm_head'}
    assert tree['moe']['we_gate'] == 'moe/we_gate'
    params = weights.make_params(cfg, 7)
    h = hashlib.sha256()
    for x in jax.tree.leaves(params):
        h.update(np.asarray(x).view(np.uint16).tobytes())
    assert h.hexdigest() == WEIGHTS_SHA_SEED_7


WEIGHTS_SHA_SEED_7 = '55470099bbc60c2259122f53612b61027957ba1fed40ec49efd4df83138b7ad2'


def test_counts_are_the_docstrings_at_a_shape_worked_out_by_hand():
    cfg = _cfg()
    fam = manifest.family(cfg)
    d, h, n = 64, 4, 4
    mla = d * 24 + 24 * h * 24 + d * 40 + 32 * h * 32 + h * 16 * d
    maps = 2 * n * d * (n * n + 2 * n)
    expert = 3 * d * 32
    outside = (3 * (mla + maps) + 3 * d * 128
               + 2 * (d * 8 + expert) + d * 512)
    assert fam.param_count(cfg) == outside + 2 * 2 * expert
    # 10 tokens, 100 (query, key) pairs
    assert fam.forward_flops(cfg, 10, 100) == (
        2.0 * fam.param_count(cfg) * 10 + 2.0 * h * (16 + 8 + 16) * 3 * 100)
    touched = 8 * (1 - (1 - 2 / 8) ** 5)
    assert fam.experts_touched(cfg, 5) == pytest.approx(touched)
    flops, nbytes = fam.decode_step(cfg, 5, 300)
    norms = 7 * d + 3 * (24 + 32)
    assert nbytes == pytest.approx(
        2 * (outside + norms + 2 * touched * expert + 300 * 3 * 40))
    assert flops == pytest.approx(
        2.0 * fam.param_count(cfg) * 5 + 2.0 * h * (40 + 32) * 3 * 300)
    # a share of the experts touches its own only
    half = fam.experts_touched(dict(cfg, expert_range=[0, 4]), 5)
    assert half == pytest.approx(touched / 2)
    # one token touches its k experts, many tokens all of them
    assert fam.experts_touched(cfg, 1) == pytest.approx(2.0)
    assert fam.experts_touched(cfg, 1000) == pytest.approx(8.0)


def test_the_cells_decode_step_reads_what_the_issue_counted():
    """At the cell's size: ~40 live rows at ~3k positions read most of
    5 x 1.41 GB of experts, 1.6 GB of other weights (the head alone is
    3584 x 131072 x 2 = 0.94 GB), 1,152 bytes a live position a layer."""
    cfg = manifest.config_of(manifest.cell('xing-docs-sessions'))
    fam = manifest.family(cfg)
    _, nbytes = fam.decode_step(cfg, 40, 40 * 3000)
    experts = 5 * fam.experts_touched(cfg, 40) * 3 * 3584 * 1024 * 2
    assert 0.91 < fam.experts_touched(cfg, 40) / 64 < 0.93
    assert 6.4e9 < experts < 6.6e9
    assert nbytes - experts - 40 * 3000 * 6 * 1152 == pytest.approx(
        1.599e9, rel=0.01)


def test_mla_decode_roofline_counts_at_a_tiny_shape():
    # 2 heads, rank 4, rope 2, 10 live positions
    flops, nbytes = mla_roofline.ops_and_bytes(2, 4, 2, 10)
    assert flops == 2 * 2 * (6 + 4) * 10 and nbytes == 6 * 2 * 10
    flops, nbytes = mla_roofline.ops_and_bytes(32, 512, 64, 1)
    assert (flops, nbytes) == (69632, 1152)


def _ctx(**kw):
    base = dict(cfg=_cfg(), trace=None, records=[], stats0={}, stats1={},
                trace_t0=0.0, trace_t1=1.0, peaks={'bf16_flops': 1e12,
                                                   'hbm_bytes_per_s': 1e9})
    return types.SimpleNamespace(**dict(base, **kw))


def test_the_kernels_reader_finds_it_by_name_and_is_silent_without_it():
    rec = types.SimpleNamespace(first=0.0, last=2.0, finished=True,
                                failed=False, prompt_len=100,
                                arrivals=[(0.0, 1)])
    ops = [tr.Op('mla_decode.21', 10.0, 500.0), tr.Op('fusion.3', 600.0, 50.0),
           tr.Op('mla_decode.20', 700.0, 300.0)]
    trace = tr.Trace([tr.Device('/device:TPU:0', ops, [])], [], 0.0, 1e9)
    got = mla_reader.read(_ctx(trace=trace, records=[rec]))
    # 101 live positions: 101 * 40 * 2 bytes at 1e9 B/s over 400 ns a call
    assert got == pytest.approx(100.0 * (101 * 80 / 1e9) / 400e-9)
    bare = tr.Trace([tr.Device('/device:TPU:0', ops[1:2], [])], [], 0.0, 1e9)
    assert mla_reader.read(_ctx(trace=bare, records=[rec])) is None
    assert mla_reader.read(_ctx(records=[rec])) is None
    llama_cfg = _load('tiny_config.json')
    assert mla_reader.read(_ctx(trace=trace, records=[rec],
                                cfg=llama_cfg)) is None


def test_the_engines_new_keys_are_read_as_deltas_or_left_out():
    s0 = {'moe_expert_load': [10, 10, 10, 10], 'kv_bytes_per_token': 240}
    s1 = {'moe_expert_load': [20, 40, 10, 10], 'kv_bytes_per_token': 240}
    ctx = _ctx(stats0=s0, stats1=s1)
    assert engine_keys.read(ctx, 'kv_bytes_per_token') == 240
    assert engine_keys.read(ctx, 'moe_load_max_over_mean') == 30 * 4 / 40
    # a program without the keys (the parent, a dense model): nothing
    for stat in ('kv_bytes_per_token', 'moe_load_max_over_mean'):
        assert engine_keys.read(_ctx(stats1={'slots': 4}), stat) is None
        assert engine_keys.read(
            _ctx(stats1={'moe_expert_load': None}), stat) is None
    with pytest.raises(ValueError):
        engine_keys.read(ctx, 'no_such_stat')


def test_the_reference_gives_a_share_only_its_own_experts_part():
    cfg = _cfg()
    fam = manifest.family(cfg)
    import jax.numpy as jnp
    params = jax.tree.map(lambda x: x.astype(jnp.float32),
                          weights.make_params(cfg, 3))
    w = jax.tree.map(lambda x: x[0], params['moe'])
    x = jax.random.normal(jax.random.PRNGKey(0), (6, 64))
    ref = fam.reference
    whole = ref.experts(x, w, cfg, None)
    shared = ref.swiglu(x, w['ws_gate'], w['ws_up'], w['ws_down'], None)
    parts = [ref.experts(x, w, dict(cfg, expert_range=r), None) - shared
             for r in ((0, 4), (4, 8))]
    assert float(jnp.max(jnp.abs(sum(parts) + shared - whole))) < 1e-5
    # the control is the same mathematics, coarser
    low = ref.experts(x, w, cfg, 'int8')
    err = float(jnp.max(jnp.abs(low - whole)))
    assert 1e-4 < err < 0.5
