"""``paged.pool_write``'s two granularities against a plain reference.

A window of positions [start, start + S) per row goes into layer ``l``
of the flat ``[L·NB, H, P, D]`` pool by whole blocks
(``paged._write_blocks``) or by ``[D]`` rows (``paged._scatter_rows``),
as ``paged.write_path`` says. Both must leave the same pool: the
window's positions hold the new rows, every other position of every
live block (the rest of a touched block included: a shared or forked
prefix, the decode positions after the window) is as it was. Only the
junk sink, block 0 of each layer, may differ: both paths throw writes
there.
"""
import jax
import numpy as np
import pytest

from skypilot_tpu.models import paged

P = 16


def _case(rng, *, b, starts, s, mb, h=8, d=128, layers=3, nb=40,
          reserved=None):
    """A pool of random values, ``b`` rows with tables of distinct live
    blocks (the first ``reserved[i]`` entries; the rest name the sink)
    and a window of ``s`` new rows each."""
    pool = rng.standard_normal((layers, nb, h, P, d)).astype(np.float32)
    ids = rng.permutation(np.arange(1, nb))[:b * mb].reshape(b, mb)
    tables = ids.astype(np.int32)
    for i, n in enumerate(reserved or [mb] * b):
        tables[i, n:] = 0
    new = rng.standard_normal((b, h, s, d)).astype(np.float32)
    return pool, tables, np.asarray(starts, np.int32), new


def _expected(pool, l, tables, starts, new, active):
    """The plain write: one position at a time, skipping inactive rows
    and positions past the table or the reservation."""
    out = pool.copy()
    mb = tables.shape[1]
    for i in range(new.shape[0]):
        if active is not None and not active[i]:
            continue
        for t in range(new.shape[2]):
            pos = starts[i] + t
            if pos // P >= mb or tables[i, pos // P] == 0:
                continue
            out[l, tables[i, pos // P], :, pos % P] = new[i, :, t]
    return out


def _write(path, pool, l, tables, starts, new, active=None):
    """Layer ``l``'s window written by ``path``, in a program."""
    write = {'blocks': paged._write_blocks, 'rows': paged._scatter_rows}

    def f(pool, l, tables, starts, new, act):
        flat, blocks = paged._layer_blocks(pool, l, tables)
        return write[path](flat, blocks, starts, new, act).reshape(
            pool.shape)

    act = None if active is None else np.asarray(active)
    # skylint: allow-jit(test-only: the write as a program holds it)
    return np.asarray(jax.jit(f)(pool, np.int32(l), tables, starts, new,
                                 act))


def _check(pool, l, tables, starts, new, active=None,
           paths=('blocks', 'rows')):
    want = _expected(pool, l, tables, starts, new, active)
    for path in paths:
        got = _write(path, pool, l, tables, starts, new, active)
        # the sinks (block 0 of each layer) take junk, by design
        np.testing.assert_array_equal(got[:, 1:], want[:, 1:], path)


@pytest.mark.parametrize('width', [16, 17, 32, 100, 256])
@pytest.mark.parametrize('offset', [0, 1, 7, 15])
def test_blocks_and_rows_write_the_same_window(offset, width):
    """One row (the shared-prefix prefill), layer 1 of 3, a prefix of
    two whole blocks and ``offset`` positions before the window."""
    rng = np.random.default_rng(offset * 1000 + width)
    start = 2 * P + offset
    mb = -(-(start + width) // P) + 2
    pool, tables, starts, new = _case(rng, b=1, starts=[start], s=width,
                                      mb=mb)
    _check(pool, 1, tables, starts, new)


@pytest.mark.parametrize('active', [None, [True, False]],
                         ids=['both-active', 'one-inactive'])
def test_two_rows_and_an_inactive_one(active):
    """Two rows at different offsets; an inactive row writes nothing
    anywhere but the sink, whatever its (stale) table names."""
    rng = np.random.default_rng(7)
    pool, tables, starts, new = _case(rng, b=2, starts=[5, 35], s=32,
                                      mb=6)
    _check(pool, 2, tables, starts, new, active)


@pytest.mark.parametrize('covered', [37, 48, 100])
def test_a_capped_window_ends_at_the_tables_end(covered):
    """``W = max_len - covered`` (the engine's cap, no multiple of P
    but for 48): the window ends on the table's last position, so the
    last of the ``ceil(W/P) + 1`` blocks a window can touch lies past
    the table and goes to the sink, never onto a live block."""
    rng = np.random.default_rng(covered)
    mb = 8
    pool, tables, starts, new = _case(rng, b=1, starts=[covered],
                                      s=mb * P - covered, mb=mb)
    _check(pool, 0, tables, starts, new)


def test_a_window_past_the_table_goes_to_the_sink():
    """Positions past the table (a window the engine's cap would have
    cut) are dropped by blocks, not clipped onto the table's last entry
    as the row scatter clips them: two updates of that live block, one
    stale, would race."""
    rng = np.random.default_rng(11)
    mb = 6
    pool, tables, starts, new = _case(rng, b=1, starts=[mb * P - 21],
                                      s=64, mb=mb)
    _check(pool, 1, tables, starts, new, paths=('blocks',))


def test_the_window_past_the_reservation_goes_to_the_sink():
    """Table entries past a row's reservation name block 0: what the
    padded tail writes there lands in the sink and nowhere else."""
    rng = np.random.default_rng(3)
    pool, tables, starts, new = _case(rng, b=1, starts=[20], s=64, mb=8,
                                      reserved=[3])
    _check(pool, 1, tables, starts, new)


def test_the_latent_row_as_stored():
    """One plane of 640-wide rows (``c_kv | k_rope`` padded to whole
    tiles), one head, as ``mla_moe``'s suffix prefill writes it."""
    rng = np.random.default_rng(640)
    pool, tables, starts, new = _case(rng, b=1, starts=[2 * P + 9],
                                      s=100, mb=12, h=1, d=640,
                                      layers=2, nb=16)
    _check(pool, 1, tables, starts, new)


@pytest.mark.parametrize('s,p,h,path', [
    (1, 16, 8, 'rows'), (4, 16, 8, 'rows'), (7, 16, 8, 'rows'),
    (8, 16, 8, 'blocks'), (16, 16, 8, 'blocks'), (256, 16, 8, 'blocks'),
    (16, 16, 2, 'rows'), (32, 16, 2, 'blocks'), (1, 16, 1, 'rows'),
    (16, 16, 1, 'rows'), (63, 16, 1, 'rows'), (64, 16, 1, 'blocks'),
    (256, 16, 1, 'blocks'), (31, 32, 4, 'rows'), (32, 32, 4, 'blocks')])
def test_write_path_at_the_threshold(s, p, h, path):
    assert paged.write_path(s, p, h) == path


@pytest.mark.parametrize('s,h', [(1, 8), (4, 8), (16, 8), (16, 1),
                                 (64, 1)])
def test_pool_write_takes_the_path_write_path_names(s, h):
    """``pool_write`` branches on ``write_path`` of the window's shape:
    the row scatter is the one that sees the pool as ``[L·NB·H·P, D]``
    rows."""
    rng = np.random.default_rng(s * 10 + h)
    pool, tables, starts, new = _case(rng, b=2, starts=[3, 30], s=s,
                                      mb=8, h=h)
    jaxpr = str(jax.make_jaxpr(lambda *a: paged.pool_write(*a, None))(
        pool, np.int32(1), tables, starts, new))
    rows = f'f32[{pool.size // pool.shape[-1]},{pool.shape[-1]}]'
    assert (rows in jaxpr) == (paged.write_path(s, P, h) == 'rows')
    # skylint: allow-jit(test-only: the write as a program holds it)
    got = jax.jit(paged.pool_write)(pool, np.int32(1), tables, starts, new,
                                    None)
    want = _expected(pool, 1, tables, starts, new, None)
    np.testing.assert_array_equal(np.asarray(got)[:, 1:], want[:, 1:])
