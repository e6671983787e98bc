"""``ops/decode_attention.kda_step`` (the Pallas interpreter, asked for
by name) against ``models/kda.recur``, the plain XLA form of the same
one-token recurrence; the rule that chooses between them; and the
decode programs of both latent families with the kernel in them. Float32
throughout: the comparison is of the mathematics. What Mosaic makes of
the kernel at the cell's widths is ``tests/test_chip_compile.py``'s."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu.models import kda, mla_moe
from skypilot_tpu.ops import attention, decode_attention
from test_kda import _eqns      # every equation of a jaxpr, nested ones too

# 10 rows: two programs of five; 3 heads: one DMA group of three
B, H, D, L = 10, 3, 128, 3
LIVE = {'none': [], 'one': [7], 'scattered': [0, 3, 4, 9],
        'all': list(range(B))}


def _inputs(seed=0, b=B, h=H, dk=D, dv=D, layers=L):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    state = jax.random.normal(ks[0], (layers, b, h, dk, dv))
    q = kda._l2(jax.random.normal(ks[1], (b, h, dk))) * dk ** -0.5
    k = kda._l2(jax.random.normal(ks[2], (b, h, dk)))
    v = jax.random.normal(ks[3], (b, h, dv))
    # decays from none at all to e^-7 a token
    g = -jnp.exp(jax.random.normal(ks[4], (b, h, dk)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[5], (b, h)))
    return state, q, k, v, g, beta


def _mask(rows, b=B):
    live = np.zeros((b,), bool)
    live[rows] = True
    return live


@pytest.mark.parametrize('layer', [0, 1, 2])
@pytest.mark.parametrize('rows', list(LIVE))
def test_the_kernel_is_the_xla_recurrence_on_the_live_rows_alone(layer, rows):
    """Live rows: output and state of ``kda.recur`` to float32
    rounding (the sums over dk go in another order). Rows not live:
    their state BIT FOR BIT, whatever g and beta hold there, and zeros
    for an output; every other layer bit for bit. No live row: the
    whole carry is what it was."""
    state, q, k, v, g, beta = _inputs(seed=layer)
    live = _mask(LIVE[rows])
    # the kernel masks by ``live`` itself: g and beta are NOT zeroed
    got_o, got_s = decode_attention.kda_step(
        state, jnp.int32(layer), q, k, v, g, beta, jnp.asarray(live),
        interpret=True)
    want_o, want_s = kda.recur(state[layer], q, k, v, g, beta)
    assert got_s.dtype == jnp.float32 and got_s.shape == state.shape
    assert got_o.dtype == jnp.float32 and got_o.shape == (B, H, D)
    if live.any():
        assert float(jnp.max(jnp.abs(got_o - want_o)[live])) < 2e-6
        assert float(jnp.max(jnp.abs(got_s[layer] - want_s)[live])) < 2e-6
        # and it did something
        assert float(jnp.max(jnp.abs(got_s[layer] - state[layer])[live])) > .1
    assert bool(jnp.all(got_s[layer][~live] == state[layer][~live]))
    assert not np.asarray(got_o)[~live].any()
    others = [i for i in range(L) if i != layer]
    assert bool(jnp.all(got_s[jnp.asarray(others)]
                        == state[jnp.asarray(others)]))
    if not live.any():
        assert bool(jnp.all(got_s == state))


@pytest.mark.parametrize('b, h, dk, dv', [(1, 1, 128, 128), (16, 8, 128, 128),
                                          (3, 12, 128, 256),
                                          (4, 2, 256, 128)])
def test_the_kernel_at_other_geometries(b, h, dk, dv):
    """One row; two programs of eight rows and one whole DMA group; 12
    heads in two groups of six, dv two lane tiles; dk two lane tiles."""
    state, q, k, v, g, beta = _inputs(seed=b, b=b, h=h, dk=dk, dv=dv,
                                      layers=2)
    live = _mask(list(range(0, b, 2)), b)
    got_o, got_s = decode_attention.kda_step(
        state, jnp.int32(1), q, k, v, g, beta, jnp.asarray(live),
        interpret=True)
    want_o, want_s = kda.recur(state[1], q, k, v, g, beta)
    assert float(jnp.max(jnp.abs(got_o - want_o)[live])) < 2e-6
    assert float(jnp.max(jnp.abs(got_s[1] - want_s)[live])) < 2e-6
    assert bool(jnp.all(got_s[1][~live] == state[1][~live]))
    assert bool(jnp.all(got_s[0] == state[0]))


def test_under_a_scan_the_state_is_the_carry_and_the_layer_is_traced():
    """As the model calls it: the layer index a traced scalar of a scan
    that carries the whole state; three layers one after the other are
    three ``kda.recur``s."""
    state, q, k, v, g, beta = _inputs(seed=5)
    live = jnp.asarray(_mask(LIVE['scattered']))
    g = jnp.where(live[:, None, None], g, 0.0)
    beta = jnp.where(live[:, None], beta, 0.0)

    def body(state, l):
        o, state = decode_attention.kda_step(state, l, q, k, v, g, beta,
                                             live, interpret=True)
        return state, o

    got_s, got_o = jax.lax.scan(body, state, jnp.arange(L, dtype=jnp.int32))
    for l in range(L):
        want_o, want_s = kda.recur(state[l], q, k, v, g, beta)
        assert float(jnp.max(jnp.abs(got_o[l] - want_o)[live])) < 2e-6
        assert float(jnp.max(jnp.abs(got_s[l] - want_s))) < 2e-6


def test_every_product_and_sum_of_the_kernel_is_float32_on_the_vpu():
    """What ``correct`` cannot see (PERF.md §7) is held here: inside the
    kernel nothing is cast, no matrix unit pass stands in for a float32
    product (no ``dot_general``), and every multiply, add and reduction
    takes and gives float32."""
    state, q, k, v, g, beta = _inputs()
    live = jnp.ones((B,), bool)
    jaxpr = jax.make_jaxpr(lambda *a: decode_attention.kda_step(
        *a, interpret=True))(state, jnp.int32(0), q, k, v, g, beta, live)
    call = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == 'pallas_call']
    assert len(call) == 1
    inner = list(_eqns(call[0].params['jaxpr']))
    names = {e.primitive.name for e in inner}
    assert 'dot_general' not in names and 'convert_element_type' not in {
        e.primitive.name for e in inner
        if any(getattr(v.aval, 'shape', ()) for v in e.outvars)}
    math = [e for e in inner if e.primitive.name in ('mul', 'add', 'sub',
                                                     'reduce_sum')
            and getattr(e.outvars[0].aval, 'shape', ())]
    assert len(math) >= 8
    assert all(v.aval.dtype == jnp.float32
               for e in math for v in list(e.invars) + list(e.outvars))
    # the decay's exponential is taken of a float32 number, outside
    exps = [e for e in _eqns(jaxpr.jaxpr) if e.primitive.name == 'exp']
    assert exps and all(e.invars[0].aval.dtype == jnp.float32 for e in exps)


# -- the rule ---------------------------------------------------------------


def test_the_rule_observes_backend_dtype_and_head_tiles(monkeypatch):
    f32_state = (4, 48, 32, 128, 128)
    # off the TPU, nobody asked for the interpreter
    assert kda.step_path(f32_state, jnp.float32) == 'xla'
    monkeypatch.setattr(decode_attention, 'PAGED_INTERPRET', True)
    assert kda.step_path(f32_state, jnp.float32) == 'kernel'
    assert kda.step_path((1, 2, 2, 256, 128), jnp.float32) == 'kernel'
    # a bfloat16 state is another result, not the kernel's to take
    assert kda.step_path(f32_state, jnp.bfloat16) == 'xla'
    # heads that are no whole lane tiles (the CPU tests' 16)
    assert kda.step_path((4, 2, 2, 16, 16), jnp.float32) == 'xla'
    assert kda.step_path((4, 2, 2, 64, 128), jnp.float32) == 'xla'
    assert kda.step_path((4, 2, 2, 128, 64), jnp.float32) == 'xla'
    monkeypatch.setattr(decode_attention, 'PAGED_INTERPRET', False)
    # a TPU needs no asking
    monkeypatch.setattr(attention, '_use_pallas', lambda: True)
    assert kda.step_path(f32_state, jnp.float32) == 'kernel'
    assert kda.step_path(f32_state, jnp.bfloat16) == 'xla'


@pytest.mark.parametrize('kernel', [False, True], ids=['xla', 'kernel'])
def test_step_layer_takes_the_path_the_rule_names(kernel, monkeypatch):
    """``kda.step_layer`` over a carried [L, B, ...] state: with the
    kernel the program holds a ``pallas_call`` and no slice of the state;
    without it the layer is sliced out and put back; both give the same
    numbers."""
    cfg = mla_moe.KdaMlaMoeConfig(**dict(
        dataclasses.asdict(mla_moe.TINY), dtype=jnp.float32,
        kda_layers=(0, 1), kda_heads=2, kda_head_dim=128, kda_gate_rank=16))
    key = jax.random.PRNGKey(0)
    layer = {name: jax.random.normal(jax.random.fold_in(key, i), shape) * 0.1
             for i, (name, (shape, _, _))
             in enumerate(kda.layer_shapes(cfg).items())}
    b = 4
    states = jax.random.normal(key, (3,) + kda.state_shape(cfg, b))
    tails = jax.random.normal(key, (3,) + kda.tail_shape(cfg, b))
    h = jax.random.normal(key, (b, cfg.d_model))
    live = jnp.asarray([True, False, True, True])
    fn = lambda st, tl: kda.step_layer(cfg, h, layer, st, tl,   # noqa: E731
                                       jnp.int32(1), live)
    want = fn(states, tails)
    if kernel:
        monkeypatch.setattr(decode_attention, 'PAGED_INTERPRET', True)
    names = [e.primitive.name
             for e in _eqns(jax.make_jaxpr(fn)(states, tails).jaxpr)]
    assert ('pallas_call' in names) == kernel
    got = fn(states, tails)
    for a, w in zip(got[1:], want[1:]):
        assert float(jnp.max(jnp.abs(a - w))) < 1e-5
    assert float(jnp.max(jnp.abs(got[0] - want[0])[live])) < 1e-5
    assert bool(jnp.all(got[1][:, 1] == states[:, 1]))


# -- the family without a state ---------------------------------------------


def test_a_model_without_kda_layers_carries_no_state_and_calls_no_kernel(
        monkeypatch):
    """``xing4.0-29b-a4b``'s programs are ``mla_moe``'s too: their carry
    holds the latent planes and two empty pytrees, and their decode
    chunk has ``mla_decode`` in it and no ``kda_step``, with the
    interpreter asked for or not."""
    cfg = mla_moe.TINY
    pool = jax.eval_shape(lambda: mla_moe.init_pool(cfg, 2, 64, 9, 16))
    assert not isinstance(pool, mla_moe.LatentStatePool)
    arr, state, conv = mla_moe._carry(pool)
    assert state is None and conv is None
    assert jax.tree.leaves((state, conv)) == []
    params = jax.eval_shape(
        lambda: mla_moe.init_params(jax.random.PRNGKey(0), cfg))

    def kernels():
        jaxpr = jax.make_jaxpr(lambda p, c: mla_moe._paged_chunk_impl(
            cfg, 2, p, c, jnp.zeros((2,), jnp.int32),
            jnp.zeros((2,), jnp.float32), None, None, jnp.ones((2,), bool),
            jax.random.PRNGKey(0)))(params, pool)
        return sorted({e.params['name'] for e in _eqns(jaxpr.jaxpr)
                       if e.primitive.name == 'pallas_call'})

    assert kernels() == []
    monkeypatch.setattr(decode_attention, 'PAGED_INTERPRET', True)
    assert kernels() == ['mla_decode']
