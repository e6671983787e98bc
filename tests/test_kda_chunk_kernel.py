"""``ops/kda_prefill.kda_chunk`` (the Pallas interpreter, asked for by
name) against ``models/kda.chunked``, the XLA form of the same chunked
recurrence; the rule that chooses between them; and the prefill's mixer
with the kernel in it. Float32 throughout: the comparison is of the
mathematics. What Mosaic makes of the kernel at the cell's widths is
``tests/test_chip_compile.py``'s.

The limits: the two forms take the same exponentials and products in
another order (sums over the channels on the vector unit here, in the
einsums there; the chunk's inverse by block rows here, by halves
there), so o and the state differ by float32 rounding: at most 3.4e-8
and 6.6e-7 (the state's entries run to ~5) over the cases below; the
limits leave ~6x."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu.models import kda, mla_moe
from skypilot_tpu.ops import attention, decode_attention, kda_prefill
from test_kda import _eqns      # every equation of a jaxpr, nested ones too

D = 128
TOL_O, TOL_S = 2e-7, 4e-6


def _inputs(s, h, seed=0, b=2, fast=-30.0):
    """``b`` rows of ``s`` positions; a second row's last 5 are masked (g =
    beta = 0, whatever q, k and v hold there). Decays a channel from
    none to e^-30 a position: most slow (the served model's median is
    e^-0.08), every 7th channel at ``fast``."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = kda._l2(jax.random.normal(ks[0], (b, s, h, D))) * D ** -0.5
    k = kda._l2(jax.random.normal(ks[1], (b, s, h, D)))
    v = jax.random.normal(ks[2], (b, s, h, D))
    g = -jnp.exp(jax.random.normal(ks[3], (b, s, h, D)) - 2.5)
    g = jnp.where(jnp.arange(D) % 7 == 3, fast, g)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, h)))
    state = jax.random.normal(ks[5], (b, h, D, D))
    lens = jnp.asarray([s, max(s - 5, 1)][:b])
    real = jnp.arange(s)[None, :] < lens[:, None]
    g = jnp.where(real[..., None, None], g, 0.0)
    beta = jnp.where(real[..., None], beta, 0.0)
    return q, k, v, g, beta, state


def _gaps(got, want):
    return tuple(float(jnp.max(jnp.abs(a - w))) for a, w in zip(got, want))


# 16: one block; 40: a chunk of 48, a padded tail; 64: one whole chunk;
# 100: two chunks, the second padded; 192: three; 512: a prefill piece
# (eight chunks, one grid step a head)
@pytest.mark.parametrize('s', [16, 40, 64, 100, 192, 512])
def test_the_kernel_is_the_chunked_form(s):
    """o and the state of ``kda.chunked`` over two rows continuing a
    non-zero state, one row's tail masked; 2 heads (1 at 512)."""
    a = _inputs(s, h=1 if s == 512 else 2, seed=s)
    want_o, want_s = kda.chunked(*a)
    got_o, got_s = kda.chunked_kernel(*a)
    assert got_o.shape == want_o.shape and got_s.shape == want_s.shape
    assert got_o.dtype == got_s.dtype == jnp.float32
    assert bool(jnp.all(jnp.isfinite(got_o)) & jnp.all(jnp.isfinite(got_s)))
    gap_o, gap_s = _gaps((got_o, got_s), (want_o, want_s))
    assert gap_o < TOL_O and gap_s < TOL_S, (gap_o, gap_s)
    # and the state moved
    assert float(jnp.max(jnp.abs(got_s - a[-1]))) > 0.1


def test_channels_that_decay_at_once_overflow_nothing():
    """Every channel at e^-20 to e^-30 a position: a block's sums run
    to -480, so a pair taken as exp(G_i) exp(-G_j) would be inf x 0;
    taken as exp(G_i - G_j) (j <= i) nothing overflows."""
    q, k, v, _, beta, state = _inputs(128, h=2, seed=3)
    g = -20.0 - 10.0 * jax.random.uniform(jax.random.PRNGKey(9), q.shape)
    a = (q, k, v, g, beta, state)
    got = kda.chunked_kernel(*a)
    assert all(bool(jnp.all(jnp.isfinite(x))) for x in got)
    gap_o, gap_s = _gaps(got, kda.chunked(*a))
    assert gap_o < TOL_O and gap_s < TOL_S, (gap_o, gap_s)


@pytest.mark.parametrize('real, width', [(100, 192), (64, 128), (70, 512)])
def test_masked_positions_leave_the_state_at_the_rows_length(real, width):
    """A row of ``real`` positions padded to ``width`` with junk q, k
    and v and g = beta = 0: its state and its real positions' o are
    those of the row alone, BIT FOR BIT (a masked position's update is
    exactly 0 and its decay exactly 1), and ``kda.chunked``'s at the
    row's own length to the limits."""
    a = _inputs(width, h=2, seed=real, b=1, fast=-5.0)
    junk = jax.random.normal(jax.random.PRNGKey(1), a[0].shape) * 3.0
    live = (jnp.arange(width) < real)[None, :, None, None]
    q, k, v = (jnp.where(live, x, junk) for x in a[:3])
    g, beta = jnp.where(live, a[3], 0.0), jnp.where(live[..., 0], a[4], 0.0)
    padded_o, padded_s = kda.chunked_kernel(q, k, v, g, beta, a[5])
    cut = (x[:, :real] for x in (q, k, v, g, beta))
    alone_o, alone_s = kda.chunked_kernel(*cut, a[5])
    assert bool(jnp.all(padded_s == alone_s))
    assert bool(jnp.all(padded_o[:, :real] == alone_o))
    want = kda.chunked(*(x[:, :real] for x in (q, k, v, g, beta)), a[5])
    gap_o, gap_s = _gaps((alone_o, alone_s), want)
    assert gap_o < TOL_O and gap_s < TOL_S, (gap_o, gap_s)


def test_a_state_left_alone_by_an_all_masked_row():
    """g = beta = 0 everywhere (a row that is not live): the state comes
    back bit for bit and o is S^T q."""
    q, k, v, _, _, state = _inputs(64, h=2, seed=4, b=1)
    zero = jnp.zeros(q.shape[:3])
    o, s = kda.chunked_kernel(q, k, v, jnp.zeros_like(q), zero, state)
    assert bool(jnp.all(s == state))
    want = jnp.einsum('bshc,bhcv->bshv', q, state,
                      precision=jax.lax.Precision.HIGHEST)
    assert float(jnp.max(jnp.abs(o - want))) < TOL_O


def test_every_product_of_the_kernel_is_float32_at_full_precision():
    """What ``correct`` cannot see is held here: inside the kernel
    nothing is cast, every matrix product takes float32 operands at
    ``Precision.HIGHEST`` into float32, and every multiply, add and
    exponential takes and gives float32."""
    a = _inputs(128, h=2)
    jaxpr = jax.make_jaxpr(lambda *x: kda_prefill.kda_chunk(
        *x, chunk=kda.CHUNK, sub=kda.SUB, interpret=True))(*a)
    call = [e for e in _eqns(jaxpr.jaxpr) if e.primitive.name == 'pallas_call']
    assert len(call) == 1 and call[0].params['name'] == 'kda_chunk'
    inner = list(_eqns(call[0].params['jaxpr']))
    dots = [e for e in inner if e.primitive.name == 'dot_general']
    assert len(dots) >= 8
    for e in dots:
        assert e.params['precision'] == (jax.lax.Precision.HIGHEST,) * 2
        assert e.params['preferred_element_type'] == jnp.float32
        assert all(v.aval.dtype == jnp.float32
                   for v in list(e.invars) + list(e.outvars))
    assert not [e for e in inner if e.primitive.name == 'convert_element_type'
                and e.params['new_dtype'] != jnp.float32
                and getattr(e.outvars[0].aval, 'shape', ())]
    math = [e for e in inner if e.primitive.name in ('mul', 'add', 'sub',
                                                     'exp', 'reduce_sum')
            and getattr(e.outvars[0].aval, 'shape', ())
            and jnp.issubdtype(e.outvars[0].aval.dtype, jnp.floating)]
    assert len(math) >= 50
    assert all(v.aval.dtype == jnp.float32
               for e in math for v in list(e.invars) + list(e.outvars)
               if getattr(v.aval, 'shape', ()))


# -- the rule ---------------------------------------------------------------


def test_the_rule_observes_backend_dtype_and_head_tiles(monkeypatch):
    state = (1, 32, 128, 128)
    # off the TPU, nobody asked for the interpreter
    assert kda.chunk_path(state, jnp.float32) == 'xla'
    monkeypatch.setattr(decode_attention, 'PAGED_INTERPRET', True)
    assert kda.chunk_path(state, jnp.float32) == 'kernel'
    assert kda.chunk_path((4, 48, 2, 256, 128), jnp.float32) == 'kernel'
    # a bfloat16 state is another result, not the kernel's to take
    assert kda.chunk_path(state, jnp.bfloat16) == 'xla'
    # heads that are no whole lane tiles (the CPU tests' 16)
    assert kda.chunk_path((1, 2, 16, 16), jnp.float32) == 'xla'
    assert kda.chunk_path((1, 2, 64, 128), jnp.float32) == 'xla'
    assert kda.chunk_path((1, 2, 128, 64), jnp.float32) == 'xla'
    monkeypatch.setattr(decode_attention, 'PAGED_INTERPRET', False)
    # a TPU needs no asking
    monkeypatch.setattr(attention, '_use_pallas', lambda: True)
    assert kda.chunk_path(state, jnp.float32) == 'kernel'
    assert kda.chunk_path(state, jnp.bfloat16) == 'xla'


@pytest.mark.parametrize('kernel', [False, True], ids=['xla', 'kernel'])
def test_the_mixers_prefill_takes_the_path_the_rule_names(kernel,
                                                          monkeypatch):
    """``kda.forward`` over a group of two rows of unequal length (200
    and 153 positions: four chunks, the second row's tail masked): with
    the kernel the program holds ONE ``kda_chunk`` (the map over rows
    calls it a row at a time) and no scan over chunks; without it the
    chunks are scanned; both give the same numbers."""
    cfg = mla_moe.KdaMlaMoeConfig(**dict(
        dataclasses.asdict(mla_moe.TINY), dtype=jnp.float32,
        kda_layers=(0, 1), kda_heads=2, kda_head_dim=128, kda_gate_rank=16))
    key = jax.random.PRNGKey(0)
    layer = {name: jax.random.normal(jax.random.fold_in(key, i), shape) * 0.1
             for i, (name, (shape, _, _))
             in enumerate(kda.layer_shapes(cfg).items())}
    h = jax.random.normal(key, (2, 200, cfg.d_model))
    state = jax.random.normal(key, kda.state_shape(cfg, 2))
    tail = jax.random.normal(key, kda.tail_shape(cfg, 2))
    lens = jnp.asarray([200, 153], jnp.int32)
    fn = lambda st, tl: kda.forward(cfg, h, layer, st, tl, lens)  # noqa: E731
    want = fn(state, tail)
    if kernel:
        monkeypatch.setattr(decode_attention, 'PAGED_INTERPRET', True)
    eqns = list(_eqns(jax.make_jaxpr(fn)(state, tail).jaxpr))
    calls = [e.params['name'] for e in eqns
             if e.primitive.name == 'pallas_call']
    assert calls == (['kda_chunk'] if kernel else [])
    # the XLA form's scan over the 4 chunks is gone with the kernel (the
    # kernel's own loop over them is inside its call)
    kernel_loops = [x for e in eqns if e.primitive.name == 'pallas_call'
                    for x in _eqns(e.params['jaxpr'])]
    scans = sum(e.primitive.name == 'scan' and e.params['length'] == 4
                for e in eqns)
    inner_scans = sum(e.primitive.name == 'scan' and e.params['length'] == 4
                      for e in kernel_loops)
    assert (scans - inner_scans > 0) != kernel
    got = fn(state, tail)
    for a, w in zip(got, want):
        assert float(jnp.max(jnp.abs(a - w))) < 1e-4
    # the second row's state and tails are taken at its own length
    assert not np.allclose(np.asarray(got[1][1]), np.asarray(state[1]))
