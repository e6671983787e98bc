"""The plain reference against the program at a tiny size, and the
control (the reference in int8) coming out as not correct."""
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmarks import correct, weights
from benchmarks.reference import llama as ref
from benchmarks.reference import train as ref_train
from benchmarks.timeline import Record

DATA = os.path.join(os.path.dirname(__file__), 'data')
with open(os.path.join(DATA, 'tiny_config.json')) as f:
    CFG = json.load(f)
TCFG = {'optimizer': 'adafactor', 'learning_rate': 1e-3, 'warmup_steps': 0,
        'total_steps': 10000, 'grad_clip_norm': 1.0}


def _program_logits(params, tokens):
    import dataclasses
    from benchmarks.serving import llama_config
    from skypilot_tpu.models import llama
    lcfg = dataclasses.replace(llama_config(CFG), dtype=jnp.float32)
    p32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    with jax.default_matmul_precision('highest'):
        return llama.forward(p32, jnp.asarray(tokens)[None], lcfg)[0]


@pytest.mark.parametrize('seed', [1, 2**31 + 3])
def test_reference_forward_agrees_with_the_programs_model(seed):
    params = weights.make_params(CFG, seed)
    toks = np.random.default_rng(seed).integers(0, CFG['vocab_size'], 48)
    rows = np.arange(48)
    got = ref.logits_at(params, toks, rows, CFG)
    want = _program_logits(params, toks)
    assert got.shape == (48, CFG['vocab_size'])
    assert float(jnp.max(jnp.abs(got - want))) < 2e-3


def test_weights_are_a_function_of_the_seed_alone():
    a, b = weights.make_params(CFG, 5), weights.make_params(CFG, 5)
    c = weights.make_params(CFG, 6)
    same = jax.tree.map(lambda x, y: bool((x == y).all()), a, b)
    assert all(jax.tree.leaves(same))
    assert not bool((a['embed'] == c['embed']).all())
    assert a['layers']['wq'].shape == (2, 64, 4, 32)
    assert a['lm_head'].dtype == jnp.bfloat16
    assert weights.param_count(CFG) == sum(
        x.size for x in jax.tree.leaves(a))


def test_padding_on_the_right_leaves_earlier_rows_alone():
    params = weights.make_params(CFG, 3)
    toks = np.random.default_rng(0).integers(0, CFG['vocab_size'], 20)
    padded = np.concatenate([toks, np.zeros(12, np.int64)])
    a = ref.logits_at(params, toks, np.arange(20), CFG)
    b = ref.logits_at(params, padded, np.arange(20), CFG)
    assert float(jnp.max(jnp.abs(a - b))) < 1e-4


def _greedy_record(params, seed, n_prompt=24, n_new=24, quant=None):
    """A request 'served' greedily by the reference itself in the
    stated precision (bfloat16 weights, float32 math)."""
    rng = np.random.default_rng(seed)
    prompt = rng.integers(0, CFG['vocab_size'], n_prompt).tolist()
    seq = list(prompt)
    for _ in range(n_new):
        padded = np.zeros(128, np.int64)
        padded[:len(seq)] = seq
        lg = ref.logits_at(params, padded, np.array([len(seq) - 1]), CFG,
                           quant)
        seq.append(int(jnp.argmax(lg[0])))
    rec = Record(rid=0, prompt_len=n_prompt, max_new=n_new, prompt=prompt)
    rec.tokens = seq[n_prompt:]
    rec.arrivals = [(0.0, n_new)]
    return rec


@pytest.mark.parametrize('seed', [1, 2, 3])
def test_the_int8_control_is_not_correct_where_greedy_tokens_are(seed):
    params = weights.make_params(CFG, seed)
    rec = _greedy_record(params, seed, 16, 112)
    sound = correct.serving_gaps(params, CFG, [rec], None, pad_lo=64)
    control = correct.serving_gaps(params, CFG, [rec], 'int8', pad_lo=64)
    assert sound['tokens'] == control['tokens'] == 112
    assert sound['gap_max'] <= 1e-4            # its own greedy tokens
    assert control['gap_max'] > 0.01           # int8 puts another first
    ok, _ = correct.verdict({**control, 'missing': 0.0},
                            {'gap_mean': 0.0001, 'missing': 0})
    assert ok is False


def test_an_unknown_control_precision_is_refused():
    params = weights.make_params(CFG, 1)
    with pytest.raises(ValueError):
        ref.logits_at(params, np.zeros(16, np.int64), np.arange(4), CFG,
                      'int3')


def _optax_steps(params, batches):
    opt = optax.chain(optax.clip_by_global_norm(1.0),
                      optax.adafactor(learning_rate=optax.
                                      warmup_cosine_decay_schedule(
                                          0.0, 1e-3, 0, 10000)))
    state = opt.init(params)
    grad_fn = ref_train.make_grad_fn(CFG)
    out = []
    for b in batches:
        loss, g = grad_fn(params, jnp.asarray(b))
        upd, state = opt.update(g, state, params)
        params = optax.apply_updates(params, upd)
        out.append(loss)
    return params, state, out


def test_reference_adafactor_follows_the_library_the_program_uses():
    """Same gradients through optax (in the parameters' bfloat16) and
    through the reference's float32 Adafactor: the parameters' change
    agrees leaf by leaf to bfloat16 rounding."""
    p0 = weights.make_params(CFG, 4)
    rng = np.random.default_rng(4)
    batches = [rng.integers(0, CFG['vocab_size'], (2, 64)) for _ in range(3)]
    want, _, _ = _optax_steps(p0, batches)
    got, opt = p0, ref_train.init_opt_state(p0)
    for b in batches:
        got, opt, _, _, _ = ref_train.train_step(got, opt, b, CFG, TCFG)
    dw = ref_train.change_sq_norms(want, p0)
    dg = ref_train.change_sq_norms(got, p0)
    for k in dw:
        assert math.sqrt(dg[k]) == pytest.approx(math.sqrt(dw[k]), rel=0.05,
                                                 abs=1e-6), k
    assert max(dw.values()) > 0


def test_first_gradients_norms_come_back_out_of_the_optimizer_state():
    p0 = weights.make_params(CFG, 9)
    batch = np.random.default_rng(9).integers(0, CFG['vocab_size'], (2, 64))
    loss, g = ref_train.make_grad_fn(CFG)(p0, jnp.asarray(batch))
    want = {k: math.sqrt(v) for k, v in ref_train.leaf_sq_norms(g).items()}
    gnorm = math.sqrt(sum(v * v for v in want.values()))
    opt = optax.chain(optax.clip_by_global_norm(1.0),
                      optax.adafactor(learning_rate=1e-3))
    _, state = opt.update(g, opt.init(p0), p0)
    got = correct.grad_norms_from_adafactor(state, p0, 1.0, gnorm)
    assert gnorm > 1.0                      # the clip did act
    for k in want:
        assert got[k] == pytest.approx(want[k], rel=0.02), k


def test_learning_rate_schedule():
    assert ref_train.lr_at(0, TCFG) == pytest.approx(1e-3)
    assert ref_train.lr_at(10000, TCFG) == pytest.approx(0.0, abs=1e-12)
    warm = dict(TCFG, warmup_steps=100)
    assert ref_train.lr_at(0, warm) == 0.0
    assert ref_train.lr_at(50, warm) == pytest.approx(5e-4)
    sched = optax.warmup_cosine_decay_schedule(0.0, 1e-3, 100, 10000)
    for t in (0, 1, 99, 100, 101, 5000):
        assert ref_train.lr_at(t, warm) == pytest.approx(float(sched(t)),
                                                         rel=1e-4, abs=1e-9)


@pytest.mark.parametrize('shape,dims', [
    ((24, 2048, 8192), (1, 2)), ((24, 2048, 16, 128), (3, 1)),
    ((24, 16, 128, 2048), (2, 3)), ((92544, 2048), (1, 0)),
    ((24, 2048), None), ((2048,), None)])
def test_factored_dims_are_the_two_largest_axes(shape, dims):
    assert ref_train.factored_dims(shape) == dims


def test_the_int8_control_fails_the_training_numbers():
    params = weights.make_params(CFG, 2)
    batch = np.random.default_rng(2).integers(0, CFG['vocab_size'], (2, 64))
    sides = {}
    for quant in (None, 'int8'):
        p, opt = params, ref_train.init_opt_state(params)
        losses = []
        for _ in range(2):
            p, opt, loss, gn, gg = ref_train.train_step(
                p, opt, batch, CFG, TCFG, quant)
            losses.append(loss)
            if len(losses) == 1:
                first = (gn, gg)
        sides[quant] = {'losses': losses, 'grad': first[0],
                        'grad_global': first[1],
                        'change': {k: math.sqrt(v) for k, v in
                                   ref_train.change_sq_norms(p, params)
                                   .items()}}
    nums = correct.train_numbers(sides['int8'], sides[None])
    assert nums['loss_gap'] > 3e-4 and nums['change_leaf_gap'] > 0.02
    ok, _ = correct.verdict(nums, {'loss_gap': 3e-4,
                                   'change_leaf_gap': 0.02})
    assert ok is False
