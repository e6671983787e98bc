"""Pallas grouped matmul for expert calls with many rows an expert.

``moe_gmm`` computes what ``jax.lax.ragged_dot`` does for the sorted
rows of a drop-free expert layer (``models/moe.dropfree_mlp``): the rows
of group ``g`` (consecutive, from row 0, ``sizes[g]`` of them) against
expert ``g``'s matrix, bf16 operands into a float32 accumulator, a result
in the operands' dtype; rows past ``sum(sizes)`` come out zero. It takes
the WHOLE scanned stack of experts [L * E, K, N] and the layer's index by
scalar prefetch, like ``decode_attention.kda_step``: a layer sliced out
in front of a Mosaic call is a copy of it (PERF.md, PR 28).

What it is for: alone on a v5e (PERF.md §6, PR 39) XLA's grouped matmul
reads the experts at 32-37% of the HBM rate once they get 8 rows each or
more (a Kimi prefill piece: 17.7 ms for its 12 calls); this kernel at
81-85% (6.8 ms). Each NON-EMPTY group's [K, N] is read once a call, by
DMA into one of two VMEM slots while the group before it is multiplied;
an empty group, and the stack's other layers, cost no DMA. The grid
walks the (group, row tile) visits of the sorted rows, as megablox's
``gmm`` does: a row tile shared by two groups is visited by each, a
group larger than the tile loops its tiles over weights already in VMEM.
Tiles past the last group are written with zeros and read nothing.
``models/moe.grouped_path`` is the rule for when.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Columns of a group's [K, N] multiplied a loop turn: [tm, K] x [K, 512].
GMM_COLS = 512
# VMEM a call may take: two slots of one expert's [K, N] and the row
# tiles' double buffers, under a v5e's 128 MiB.
GMM_VMEM_CAP_BYTES = 96 * 1024 * 1024


def _cols(n: int) -> int:
    c = min(GMM_COLS, n)
    while n % c:
        c -= 128
    return c


def _vmem_bytes(tm: int, k: int, n: int, itemsize: int) -> int:
    # two weight slots, lhs and out tiles double-buffered, the float32
    # product of a loop turn
    return (2 * k * n + 4 * tm * (k + n)) * itemsize + 4 * tm * _cols(n)


def gmm_fits(tm: int, k: int, n: int, dtype) -> bool:
    """True when ``moe_gmm`` takes [*, K] x [K, N] in row tiles of ``tm``:
    a float dtype, K and N whole 128-lane tiles, ``tm`` whole (sublane)
    tiles of the dtype, and two experts' weights in VMEM."""
    dtype = jnp.dtype(dtype)
    if not jnp.issubdtype(dtype, jnp.floating):
        return False
    sublane = 8 * (4 // dtype.itemsize)
    return (k % 128 == 0 and n % 128 == 0 and tm % sublane == 0
            and _vmem_bytes(tm, k, n, dtype.itemsize) + (8 << 20)
            <= GMM_VMEM_CAP_BYTES)


def schedule(sizes: jax.Array, m: int, tm: int):
    """The grid's visits for rows sorted into ``sizes`` [E] groups, in
    row tiles of ``tm`` (``m % tm == 0``). A visit t is (group, tile):
    every non-empty group's tiles in order, then the tiles past the last
    group (group ``E``: zeros). -> int32 arrays (starts [E], ends [E],
    group [T], tile [T], lhs_tile [T] (the tile a visit READS: a tile
    past the groups reads the last one read, i.e. nothing new), ord [E]
    (a group's place among the non-empty ones), order [E] (the non-empty
    groups in order, then zeros), count [1] (non-empty groups)) and the
    number of visits; T = m / tm + E bounds it. Comparisons and sums
    only: every program that holds the kernel traces this at start-up."""
    e = sizes.shape[0]
    tiles = m // tm
    ends = jnp.cumsum(sizes).astype(jnp.int32)
    starts = ends - sizes
    full = sizes > 0
    first = starts // tm
    n_tiles = jnp.where(full, (ends - 1) // tm - first + 1, 0)
    done = jnp.cumsum(n_tiles)               # visits through group g
    visits = done[-1]
    t = jnp.arange(tiles + e, dtype=jnp.int32)
    # the group of visit t: the first whose visits reach past t (E after)
    grp = jnp.sum(done[None, :] <= t[:, None], axis=1, dtype=jnp.int32)
    g = jnp.minimum(grp, e - 1)
    own = first[g] + t - (done[g] - n_tiles[g])
    in_groups = t < visits
    used = (ends[-1] + tm - 1) // tm          # tiles the groups touch
    tile = jnp.where(in_groups, own, used + t - visits)
    lhs_tile = jnp.where(in_groups, own, jnp.maximum(used - 1, 0))
    ords = jnp.cumsum(full, dtype=jnp.int32) - 1
    ids = jnp.arange(e, dtype=jnp.int32)
    order = jnp.sum(jnp.where(full[None, :] & (ords[None, :] == ids[:, None]),
                              ids[None, :], 0), axis=1, dtype=jnp.int32)
    count = jnp.sum(full, dtype=jnp.int32)
    meta = (starts, ends, grp, tile.astype(jnp.int32),
            lhs_tile.astype(jnp.int32), ords, order, count[None])
    return meta, visits + tiles - used


def _gmm_kernel(layer_ref, starts_ref, ends_ref, group_ref, tile_ref,
                _lhs_tile_ref, ord_ref, order_ref, count_ref, lhs_ref,
                rhs_hbm, out_ref, w_buf, sem, *, groups: int, tm: int,
                cols: int):
    """One visit: lhs_ref [tm, K] (the visit's row tile), out_ref
    [tm, N], rhs_hbm the whole stack [L * E, K, N] left in HBM, w_buf
    [2, K, N] the two weight slots. A group's first visit waits for its
    weights (started by the group before it; the first group starts its
    own) and starts the next non-empty group's into the other slot."""
    t = pl.program_id(0)
    g = group_ref[t]
    before = jnp.maximum(t - 1, 0)
    new_tile = (t == 0) | (tile_ref[before] != tile_ref[t])
    real = g < groups

    def copy(gi, slot):
        src = rhs_hbm.at[layer_ref[0] * groups + gi]
        return pltpu.make_async_copy(src, w_buf.at[slot], sem.at[slot])

    @pl.when(real & ((t == 0) | (group_ref[before] != g)))
    def _():
        o = ord_ref[g]

        @pl.when(o == 0)
        def _():
            copy(g, 0).start()

        copy(g, o % 2).wait()

        @pl.when(o + 1 < count_ref[0])
        def _():
            copy(order_ref[o + 1], 1 - o % 2).start()

    @pl.when(real)
    def _():
        slot = ord_ref[g] % 2
        rows = tile_ref[t] * tm + jax.lax.broadcasted_iota(
            jnp.int32, (tm, 1), 0)
        mine = (rows >= starts_ref[g]) & (rows < ends_ref[g])
        x = lhs_ref[...]

        def part(j, carry):
            c = pl.ds(pl.multiple_of(j * cols, cols), cols)
            y = jnp.dot(x, w_buf[slot, :, c],
                        preferred_element_type=jnp.float32)
            kept = jnp.where(new_tile, jnp.zeros((tm, cols), out_ref.dtype),
                             out_ref[:, c])
            out_ref[:, c] = jnp.where(mine, y.astype(out_ref.dtype), kept)
            return carry

        jax.lax.fori_loop(0, out_ref.shape[1] // cols, part, 0,
                          unroll=True)

    @pl.when(jnp.logical_not(real))
    def _():
        out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)


# Traced once a shape a process and lowered once a shape a program: a
# prefill program holds nine calls of two shapes, and tracing and
# lowering each kernel anew cost every start-up seconds (PERF.md, PR 39).
# skylint: allow-jit(an inner call of the programs that hold it, not a
# program of its own)
@functools.partial(jax.jit, static_argnames=('tm', 'interpret'))
def moe_gmm(lhs: jax.Array, rhs: jax.Array, sizes: jax.Array,
            layer=None, *, tm: int, interpret: bool = False) -> jax.Array:
    """``lhs [M, K]`` rows sorted by group, ``sizes [E]`` int32 rows a
    group, ``rhs [L * E, K, N]``: group ``g``'s rows times
    ``rhs[layer * E + g]`` (``layer`` an int32 scalar, may be traced;
    None: ``rhs`` is the E experts alone). -> ``[M, N]`` in lhs's dtype,
    float32 accumulation, zero past ``sum(sizes)``: ``ragged_dot(lhs,
    rhs[layer * E:][:E], sizes)``. Callers gate on ``gmm_fits`` and pad
    M to a multiple of ``tm``."""
    m, k = lhs.shape
    e = sizes.shape[0]
    n = rhs.shape[-1]
    if m % tm or rhs.shape[1] != k or rhs.shape[0] % e:
        raise ValueError(f'moe_gmm: lhs {lhs.shape}, rhs {rhs.shape}, '
                         f'{e} groups, row tile {tm}')
    meta, visits = schedule(sizes.astype(jnp.int32), m, tm)
    layer = jnp.zeros((1,), jnp.int32) if layer is None else (
        jnp.reshape(layer, (1,)).astype(jnp.int32))
    cols = _cols(n)
    item = jnp.dtype(lhs.dtype).itemsize

    def row_tile(t, *refs):
        return refs[5][t], 0     # lhs_tile

    def out_tile(t, *refs):
        return refs[4][t], 0     # tile

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1 + len(meta), grid=(visits,),
        in_specs=[pl.BlockSpec((tm, k), row_tile),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((tm, n), out_tile),
        scratch_shapes=[pltpu.VMEM((2, k, n), rhs.dtype),
                        pltpu.SemaphoreType.DMA((2,))])
    return pl.pallas_call(
        functools.partial(_gmm_kernel, groups=e, tm=tm, cols=cols),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('arbitrary',),
            vmem_limit_bytes=_vmem_bytes(tm, k, n, item) + (8 << 20)),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=(e * k * n + m * (k + n)) * item),
        interpret=interpret, name='moe_gmm',
    )(layer, *meta, lhs, rhs)
