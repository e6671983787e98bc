"""Pallas decode kernels: one new token against what a slot has cached.

Reference analog: the reference's serving engines carry fused decode
attention kernels (JetStream's pallas kernels, vLLM's paged attention);
the hot op here is the decode step's attention over the KV cache —
[B, Hq, D] queries against every live key/value, every token.

Two kernels, one online softmax (the training kernel's recipe,
``ops/attention.py``; float32 logits, softmax and accumulation,
probabilities cast to the query's dtype before P·V):

``paged_decode`` — the paged layout's decode step, cache write AND
read, ON BY ITSELF wherever it fits (``models/paged.decode_path``: a
TPU, S = 1, a float pool, ``paged_fits``). It reads each slot's blocks
out of one layer of the WHOLE pool [L, NB, Hkv, P, D] through the block
table, only as far as the slot's length: the layer, tables and lengths
arrive by scalar prefetch, the pool stays in HBM (a plane sliced out for
the call would be a copy of it a layer a step), a program per slot loops
over groups of ``PAGED_GROUP`` blocks, each block one DMA ([Hkv, P, D]:
32 KB contiguous at 8 x 16 x 128 bf16) into double-buffered VMEM. No
dense view is built and no logits tensor exists in HBM. The step's new
K/V row is written by the same program (PR 31): the block of position
``valid - 1`` is always in the last group fetched, so the row is put
into it in VMEM before the group is multiplied (the token attends to
itself in the values the pool will hold) and that one block is DMA'd
back while it is; the pools are aliased input -> output, so under the
layer scan and the step scan they stay the in-place carry. An empty slot
costs a grid step (~0.35 us), reads nothing and writes nothing. Measured
alone on a v5e (my chip run, PR 31, PERF.md §6) at 48 slots, 2,049
blocks of 16, 16/8 heads x 128, a call with | without the write:
35.9 | 33.7 us with 3 rows live at ~230 positions, 80.7 | 77.3 with 24
at ~230, 195.0 | 192.0 with 20 at ~1,400 (+0.15 us a row); XLA's row
scatter it replaced (K and V, ``paged._scatter_rows``) 62.6 us whatever
the slots hold, and the gather + einsum before PR 26 2.7 ms.

``mla_decode`` — the same walk over a latent (MLA) pool in the absorbed
form: one plane of ``c_kv | k_rope`` rows serves as keys and values
(``models/mla_moe.decode_path`` is its rule).

``kda_step`` — no attention: the one-token recurrence of a Kimi Delta
Attention layer (``models/kda.py``), whose cache is a float32 STATE
[H, dk, dv] a slot, not keys and values. The WHOLE carried state
[L, slots, H, dk, dv] stays in HBM, aliased input -> output like the
pools; a live slot's state comes in once, is decayed and updated on the
VPU in float32, and goes back once; a slot that is not live is neither
read nor written (``models/kda.step_path`` is its rule).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Paged decode: single-position attention read straight out of the
# block pool through the block table.

# Blocks fetched and attended per loop turn (16 x P=16: 256 positions).
# On a v5e at 48 slots x 8 kv heads x 128 (PR 26, the kernel before it
# wrote), 4 / 8 / 16 / 32 read 76 / 66 / 70 / 81 us a call with 24 rows
# live at ~230 positions and 450 / 328 / 303 / 314 us with 32 rows at
# ~1,500 (the DMAs alone: 284).
PAGED_GROUP = 16
# Scalar-prefetched tables + lengths live in SMEM for the whole call. A
# v5e has 1 MiB of it: 512 slots x 128 blocks (256 KiB) compiles, 1,024
# x 256 (1 MiB) is refused.
PAGED_SMEM_CAP_BYTES = 256 * 1024
# Tests and the CPU rehearsal run the kernel in the Pallas interpreter by
# setting this BY NAME (monkeypatch); nothing infers it from the backend.
PAGED_INTERPRET = False


def _pick_group(n: int, cap: int = PAGED_GROUP) -> int:
    """Largest divisor of ``n`` that is <= ``cap``: a group of blocks
    never reads past the end of a table row of ``n``."""
    g = min(cap, n)
    while n % g:
        g -= 1
    return g


def paged_fits(slots: int, max_blocks: int, block: int, head_dim: int,
               dtype) -> bool:
    """True when ``paged_decode`` can take this pool geometry: the
    tables and lengths fit SMEM, a block's [P, D] is a whole number of
    the dtype's (sublane, lane) tiles so every DMA lands tile-aligned,
    and the pool holds what the kernel multiplies (a float dtype; int8
    pools carry scales the kernel does not fold)."""
    dtype = jnp.dtype(dtype)
    if not jnp.issubdtype(dtype, jnp.floating):
        return False
    sublane = 8 * (4 // dtype.itemsize)
    lanes = -(-max_blocks // 128) * 128  # SMEM pads the minor dim
    return (head_dim % 128 == 0 and block % sublane == 0
            and (slots * lanes + slots) * 4 <= PAGED_SMEM_CAP_BYTES)


def _paged_kernel(layer_ref, tables_ref, valid_ref, q_ref, kn_ref, vn_ref,
                  _k_in, _v_in, o_ref, k_hbm, v_hbm, k_buf, v_buf, sem, *,
                  block: int, group: int):
    """One slot per program. q_ref/o_ref [Hkv, G, D]; kn_ref/vn_ref
    [Hkv, 1, D] the step's new row; k_hbm/v_hbm the WHOLE pools
    [L, NB, Hkv, P, D], left in HBM, of which layer ``layer_ref[0]`` is
    read and written (the OUTPUT refs: ``_k_in``/``_v_in`` are the same
    buffers, aliased); tables_ref [B, MB] and valid_ref [B]
    scalar-prefetched too. The slot's blocks arrive
    ``group`` at a time by DMA into k_buf/v_buf [2, Hkv, group*P, D]
    (double-buffered: the next group is in flight while this one is
    multiplied), only as far as ``valid`` reaches. The last group holds
    the block of position ``valid - 1``: the new row is put into it in
    VMEM before the group is multiplied, and that one block goes back
    to the pool while it is."""
    b = pl.program_id(0)
    q = q_ref[...]
    hkv, g, d = q.shape
    span = group * block
    scale = d ** -0.5
    # The row is WRITTEN where the scatter wrote it (offset by the
    # length as it is) and READ as far as its table reaches.
    new_pos = valid_ref[b] - 1
    valid = jnp.minimum(valid_ref[b], tables_ref.shape[1] * block)
    n_blocks = pl.cdiv(valid, block)
    n_groups = pl.cdiv(valid, span)
    planes = ((k_hbm, k_buf, kn_ref, 0), (v_hbm, v_buf, vn_ref, 1))

    @pl.when(b == 0)
    def _():
        # A group's blocks past the row's length are not fetched; their
        # probabilities are exactly 0, and 0 x (whatever an
        # uninitialized buffer holds) must not be NaN. Later programs
        # find finite K/V of earlier rows there.
        v_buf[...] = jnp.zeros(v_buf.shape, v_buf.dtype)

    def group_dma(gi, slot, act):
        for j in range(group):
            i = gi * group + j

            @pl.when(i < n_blocks)
            def _(i=i, j=j):
                blk = tables_ref[b, i]
                dst = pl.ds(j * block, block)
                for plane, buf, _, s in planes:
                    act(pltpu.make_async_copy(
                        plane.at[layer_ref[0], blk],
                        buf.at[slot, :, dst, :], sem.at[s, slot]))

    def write_back(slot, rows, blk, s):
        plane, buf = planes[s][:2]
        return pltpu.make_async_copy(buf.at[slot, :, rows, :],
                                     plane.at[layer_ref[0], blk],
                                     sem.at[2, s])

    group_dma(0, 0, lambda c: c.start())

    def body(gi, carry):
        acc, m_prev, l_prev = carry
        slot = gi % 2

        @pl.when(gi + 1 < n_groups)
        def _():
            group_dma(gi + 1, 1 - slot, lambda c: c.start())

        group_dma(gi, slot, lambda c: c.wait())

        @pl.when(gi + 1 == n_groups)
        def _():
            i = n_blocks - 1
            rows = pl.ds(pl.multiple_of((i - gi * group) * block, block),
                         block)
            here = jax.lax.broadcasted_iota(
                jnp.int32, (hkv, block, d), 1) == new_pos % block
            for _, buf, new, s in planes:
                buf[slot, :, rows, :] = jnp.where(here, new[...],
                                                  buf[slot, :, rows, :])
                write_back(slot, rows, tables_ref[b, i], s).start()

        k = k_buf[slot].astype(q.dtype)  # [Hkv, span, D]
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale  # [Hkv, G, span]
        ki = gi * span + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(ki < valid, s, _NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        v = v_buf[slot].astype(q.dtype)
        acc = acc * alpha + jax.lax.dot_general(
            p.astype(q.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)  # [Hkv, G, D]
        return acc, m_new, l_new

    acc0 = jnp.zeros((hkv, g, d), jnp.float32)
    m0 = jnp.full((hkv, g, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((hkv, g, 1), jnp.float32)
    acc, _, l = jax.lax.fori_loop(0, n_groups, body, (acc0, m0, l0))
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)

    @pl.when(valid > 0)
    def _():
        # The next program refills these buffers: the block is out
        # first. (A wait counts bytes; any block's descriptor does.)
        for s in (0, 1):
            write_back(0, pl.ds(0, block), 0, s).wait()


def paged_decode(q: jax.Array, k_new: jax.Array, v_new: jax.Array,
                 k_pool: jax.Array, v_pool: jax.Array, layer: jax.Array,
                 tables: jax.Array, valid: jax.Array,
                 interpret: bool = False):
    """One decode step's cache write and read of layer ``layer`` (int32
    scalar) of the pools [L, NB, Hkv, P, D] under block tables [B, MB]
    int32. Row b's new key and value ``k_new``/``v_new`` [B, Hkv, D]
    (the pool's dtype) are written at position valid[b] - 1 of the
    blocks its table names, and q [B, Hq, D] attends positions
    < valid[b] of them in order, the new one included: the row goes
    into the block the kernel holds in VMEM anyway, and that block alone
    goes back. No other layer and no other block is read or written.
    valid[b] == 0 reads nothing, WRITES nothing and returns zeros. A
    live row appends only into a block it owns (the engine forks a
    shared tail at admission), so no program reads a block another
    writes; rows that decode on past their reservation all name the
    junk sink there, and the programs run in order, each with its block
    out before the next starts. -> (out [B, Hq, D], k_pool, v_pool):
    the pools are aliased to their operands, in place under a scan that
    carries them. Callers gate on ``paged_fits``."""
    b, hq, d = q.shape
    _, nb, hkv, block, _ = k_pool.shape
    mb = tables.shape[1]
    g = hq // hkv
    group = _pick_group(mb)
    # What XLA's gather does for the dense view, a DMA does not: keep
    # every address inside the pool. A row that finished mid-chunk
    # decodes on past max_len (the kernel clips what it reads to the
    # table, so the write lands in the row's last block as the
    # scatter's did; its output is dropped); a table never names a
    # block past the pool.
    valid = jnp.maximum(valid.astype(jnp.int32), 0)
    tables = jnp.clip(tables.astype(jnp.int32), 0, nb - 1)
    qspec = pl.BlockSpec((None, hkv, g, d), lambda bi, *_: (bi, 0, 0, 0))
    newspec = pl.BlockSpec((None, hkv, 1, d), lambda bi, *_: (bi, 0, 0, 0))
    anyspec = pl.BlockSpec(memory_space=pl.ANY)
    buf = pltpu.VMEM((2, hkv, group * block, d), k_pool.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(b,),
        in_specs=[qspec, newspec, newspec, anyspec, anyspec],
        out_specs=[qspec, anyspec, anyspec],
        # reads [K | V, buffer]; write-backs [2, K | V]
        scratch_shapes=[buf, buf, pltpu.SemaphoreType.DMA((3, 2))])
    pool = jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype)
    out, k_pool, v_pool = pl.pallas_call(
        functools.partial(_paged_kernel, block=block, group=group),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype), pool,
                   pool],
        # operands count the scalar-prefetched three
        input_output_aliases={6: 1, 7: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('arbitrary',)),
        interpret=interpret, name='paged_decode',
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), tables, valid,
      q.reshape(b, hkv, g, d),
      k_new.astype(k_pool.dtype).reshape(b, hkv, 1, d),
      v_new.astype(v_pool.dtype).reshape(b, hkv, 1, d), k_pool, v_pool)
    return out.reshape(b, hq, d), k_pool, v_pool


# ---------------------------------------------------------------------------
# Latent (MLA) paged decode: the absorbed form. One shared "KV head" of
# ``R + Dr`` numbers a position (the compressed c_kv and the one rotary
# key), all query heads against it, and the values are the first ``R``
# columns of the same rows: the plane is read ONCE.


def mla_fits(slots: int, max_blocks: int, block: int, dtype) -> bool:
    """``paged_fits`` for a latent plane: a float pool, blocks of whole
    sublane tiles, rows of whole lane tiles (``latent_width``), tables
    and lengths inside SMEM."""
    return paged_fits(slots, max_blocks, block, 128, dtype)


def latent_width(rank: int, rope_dim: int) -> int:
    """Width of a latent row in the pool: ``rank + rope_dim`` rounded up
    to whole 128-lane tiles, the tail zero. HBM arrays are tiled
    (8, 128) x dtype packing, so a [.., P, 576] plane occupies 640
    columns a row whether it says so or not (and 512 + 64 as two planes
    would occupy 512 + 128); Mosaic refuses to slice 576 of the 640 for
    a DMA ("must be aligned to tiling (128)"), so the plane says so."""
    return -(-(rank + rope_dim) // 128) * 128


def _mla_kernel(layer_ref, tables_ref, valid_ref, q_ref, kv_hbm, o_ref,
                kv_buf, sem, *, block: int, group: int, rank: int,
                scale: float):
    """One slot per program. q_ref [H, W] (the absorbed queries, zero
    past R + Dr), o_ref [H, R]; kv_hbm the WHOLE latent pool
    [L, NB, 1, P, W], left in HBM, of which layer ``layer_ref[0]`` is
    read. ``_paged_kernel``'s walk (groups of blocks by DMA into a
    double buffer, as far as ``valid`` reaches) and online softmax;
    the values are ``kv_buf[..., :rank]``: no second plane, no second
    DMA."""
    b = pl.program_id(0)
    q = q_ref[...]
    h = q.shape[0]
    span = group * block
    valid = valid_ref[b]
    n_blocks = pl.cdiv(valid, block)
    n_groups = pl.cdiv(valid, span)

    @pl.when(b == 0)
    def _():
        # see _paged_kernel: 0 x (uninitialized VMEM) must not be NaN
        kv_buf[...] = jnp.zeros(kv_buf.shape, kv_buf.dtype)

    def group_dma(gi, slot, act):
        for j in range(group):
            i = gi * group + j

            @pl.when(i < n_blocks)
            def _(i=i, j=j):
                act(pltpu.make_async_copy(
                    kv_hbm.at[layer_ref[0], tables_ref[b, i], 0],
                    kv_buf.at[slot, pl.ds(j * block, block), :],
                    sem.at[slot]))

    group_dma(0, 0, lambda c: c.start())

    def body(gi, carry):
        acc, m_prev, l_prev = carry
        slot = gi % 2

        @pl.when(gi + 1 < n_groups)
        def _():
            group_dma(gi + 1, 1 - slot, lambda c: c.start())

        group_dma(gi, slot, lambda c: c.wait())
        kv = kv_buf[slot].astype(q.dtype)                 # [span, R + Dr]
        s = jax.lax.dot_general(
            q, kv, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [H, span]
        ki = gi * span + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(ki < valid, s, _NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            p.astype(q.dtype), kv[:, :rank], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # [H, R]
        return acc, m_new, l_new

    acc0 = jnp.zeros((h, rank), jnp.float32)
    m0 = jnp.full((h, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((h, 1), jnp.float32)
    acc, _, l = jax.lax.fori_loop(0, n_groups, body, (acc0, m0, l0))
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def mla_decode(q: jax.Array, pool: jax.Array, layer: jax.Array,
               tables: jax.Array, valid: jax.Array, rank: int,
               scale: float, interpret: bool = False) -> jax.Array:
    """Absorbed queries q [B, H, R + Dr] (the single decode position)
    against layer ``layer`` (int32 scalar) of the latent pool
    [L, NB, 1, P, W] (W = ``latent_width``: rows ``c_kv | k_rope | 0``)
    under block tables [B, MB]: row b
    attends positions < valid[b]; the values are the rows' first
    ``rank`` columns. -> [B, H, R] (still latent: the caller
    up-projects). ``scale`` multiplies the logits. valid[b] == 0 reads
    nothing and returns zeros. Callers gate on ``mla_fits``."""
    b, h, _ = q.shape
    _, nb, _, block, width = pool.shape
    q = jnp.pad(q, ((0, 0), (0, 0), (0, width - q.shape[-1])))
    mb = tables.shape[1]
    group = _pick_group(mb)
    valid = jnp.clip(valid.astype(jnp.int32), 0, mb * block)
    tables = jnp.clip(tables.astype(jnp.int32), 0, nb - 1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(b,),
        in_specs=[pl.BlockSpec((None, h, width), lambda bi, *_: (bi, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((None, h, rank), lambda bi, *_: (bi, 0, 0)),
        scratch_shapes=[pltpu.VMEM((2, group * block, width), pool.dtype),
                        pltpu.SemaphoreType.DMA((2,))])
    return pl.pallas_call(
        functools.partial(_mla_kernel, block=block, group=group, rank=rank,
                          scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, rank), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('arbitrary',)),
        interpret=interpret, name='mla_decode',
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), tables, valid, q, pool)


# ---------------------------------------------------------------------------
# KDA decode step: the one-token delta-rule recurrence over the state of
# the LIVE rows, in place.

# Heads a DMA: 8 x [128, 128] float32 = 512 KB. A live row's state comes
# in as H / 8 copies started together and goes back group by group while
# the next group is computed; a group's heads are unrolled together. On a
# v5e at 48 slots x 32 heads (PR 34), 4 / 8 / 16 / 32 read 16.1 / 17.4 /
# 17.7 / 19.9 us a call with one row live and 383 / 385 / 435 / 540 with
# all 48 (XLA's three passes: 477 whatever is live).
KDA_HEADS_PER_DMA = 8
# Rows a program (the grid is B / this): a row that is not live costs a
# loop turn, not a grid step with its block copies.
KDA_ROWS = 8


def kda_fits(state_shape, dtype) -> bool:
    """True when ``kda_step`` can take a state [..., H, dk, dv]: float32
    (the recurrence's precision IS the state's), and a head's [dk, dv]
    a whole number of (8, 128) tiles with ``dk`` a whole number of lanes
    too (the step's k, q and decay arrive with dk on the lanes and are
    turned onto the sublanes a head at a time)."""
    dk, dv = state_shape[-2:]
    return (jnp.dtype(dtype) == jnp.float32 and dk % 128 == 0
            and dv % 128 == 0)


def _kda_kernel(layer_ref, live_ref, cols_ref, rows_ref, _s_in, o_ref,
                s_hbm, buf, sem, *, group: int):
    """``KDA_ROWS`` rows a program, one after the other. cols_ref
    [R, H, 3, dk]: a row's decay ``exp g``, k and q a head; rows_ref
    [R, H, 3, dv]: v, beta and k . q, the last two repeated along dv;
    o_ref [R, H, dv]. s_hbm the WHOLE state [L, B, H, dk, dv], left in
    HBM (the OUTPUT ref: ``_s_in`` is the same buffer, aliased);
    layer_ref [1] and live_ref [B] scalar-prefetched. A live row's
    [H, dk, dv] comes into ``buf`` by DMA in groups of ``group`` heads,
    is updated there head by head and goes back group by group; a row
    that is not live starts no copy. The heads are LOOPS, not Python
    unrolling: 32 bodies to trace and lower in every program that holds
    the kernel cost every start-up seconds (PERF.md, PR 34)."""
    i = pl.program_id(0)
    n_rows = cols_ref.shape[0]
    h = buf.shape[0]
    n_groups = h // group

    def copy(b, g, back):
        heads = pl.ds(g * group, group)
        hbm = s_hbm.at[layer_ref[0], b, heads]
        if back:
            return pltpu.make_async_copy(buf.at[heads], hbm, sem.at[1, g])
        return pltpu.make_async_copy(hbm, buf.at[heads], sem.at[0, g])

    def row(r, carry):
        b = i * n_rows + r

        def heads(g, carry):
            def head(j, carry):
                hh = g * group + j
                # a head's vectors lie along the state's dk, the
                # sublanes: [3, dk] -> [dk, 3], a column each
                cols = cols_ref[r, hh].T
                a, k, q = (cols[:, n:n + 1] for n in range(3))
                v, beta, kq = (rows_ref[r, hh, n:n + 1, :] for n in range(3))
                # models/kda.recur, in its order: decay, the decayed
                # state's two products, the rank-one update
                s = buf[hh] * a
                sk = jnp.sum(s * k, axis=0, keepdims=True)  # S^T k [1, dv]
                sq = jnp.sum(s * q, axis=0, keepdims=True)
                u = beta * (v - sk)
                buf[hh] = s + k * u
                o_ref[r, pl.ds(hh, 1), :] = sq + kq * u
                return carry

            copy(b, g, False).wait()
            # traced once, unrolled where Mosaic lowers the loop: the
            # group's heads overlap in the schedule
            jax.lax.fori_loop(0, group, head, 0, unroll=True)
            copy(b, g, True).start()
            return carry

        @pl.when(live_ref[b] == 0)
        def _():
            o_ref[r] = jnp.zeros(o_ref.shape[1:], o_ref.dtype)

        @pl.when(live_ref[b] != 0)
        def _():
            for g in range(n_groups):
                copy(b, g, False).start()
            jax.lax.fori_loop(0, n_groups, heads, 0)
            # the next live row refills the buffer: this one is out first
            for g in range(n_groups):
                copy(b, g, True).wait()
        return carry

    jax.lax.fori_loop(0, n_rows, row, 0)


def kda_step(state: jax.Array, layer: jax.Array, q: jax.Array,
             k: jax.Array, v: jax.Array, g: jax.Array, beta: jax.Array,
             live: jax.Array, interpret: bool = False):
    """One token a row of the KDA recurrence (``models/kda.recur``) over
    layer ``layer`` (int32 scalar: the index among the KDA layers) of
    the WHOLE state [L, B, H, dk, dv] float32. q, k, g [B, H, dk],
    v [B, H, dv], beta [B, H] float32 as ``kda.step_inputs`` makes them;
    live [B] bool. For a live row and head: ``S <- Diag(exp g) S``,
    ``u = beta (v - S^T k)``, ``S <- S + k u^T``, ``o = S^T q``, every
    product and sum float32 on the VPU; its [dk, dv] is read once and
    written once. A row that is not live is NEITHER READ NOR WRITTEN
    (its state stays bit for bit, whatever g and beta say) and its ``o``
    is ZEROS (the XLA form gives ``S^T q`` of the untouched state there;
    the engine drops either). -> (o [B, H, dv], state): the state is
    aliased to its operand, in place under a scan that carries it.
    Callers gate on ``kda_fits``."""
    b, h, dk = q.shape
    dv = v.shape[-1]
    if state.shape[1:] != (b, h, dk, dv):
        raise ValueError(f'state {state.shape} is not [L, {b}, {h}, {dk}, '
                         f'{dv}]: a row of q, k, v a row of the state')
    n_rows = _pick_group(b, KDA_ROWS)
    group = _pick_group(h, KDA_HEADS_PER_DMA)
    f32 = jnp.float32
    cols = jnp.stack([jnp.exp(g.astype(f32)), k, q], 2).astype(f32)
    wide = lambda x: jnp.broadcast_to(x[..., None], (b, h, dv))  # noqa: E731
    rows = jnp.stack([v, wide(beta), wide(jnp.sum(k * q, axis=-1))],
                     2).astype(f32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(b // n_rows,),
        in_specs=[
            pl.BlockSpec((n_rows, h, 3, dk), lambda i, *_: (i, 0, 0, 0)),
            pl.BlockSpec((n_rows, h, 3, dv), lambda i, *_: (i, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[pl.BlockSpec((n_rows, h, dv), lambda i, *_: (i, 0, 0)),
                   pl.BlockSpec(memory_space=pl.ANY)],
        # a row's whole state; [reads | write-backs, group]
        scratch_shapes=[pltpu.VMEM((h, dk, dv), f32),
                        pltpu.SemaphoreType.DMA((2, h // group))])
    o, state = pl.pallas_call(
        functools.partial(_kda_kernel, group=group),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, h, dv), f32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operands count the scalar-prefetched two
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('arbitrary',)),
        interpret=interpret, name='kda_step',
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), live.astype(jnp.int32),
      cols, rows, state)
    return o, state
