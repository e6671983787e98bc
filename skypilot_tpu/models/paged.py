"""Paged (block-table) KV cache for the continuous serving engine.

Reference analog: paged attention is the defining memory innovation of
the reference's serving workloads (``/root/reference/llm/vllm/`` — the
vLLM recipes its TPU serving docs are built around). A cache that
pins one full ``[max_len]`` row per slot strands HBM in tail padding
under mixed-length traffic (a 64-token chat in a 4096-max_len slot
wastes 98% of its row). The paged layout, the engine's only one
(``models/engine.py``), carves the cache into fixed-size position
BLOCKS shared from one pool; each slot holds a small block table, requests reserve only
``ceil((prompt + max_new) / block) `` blocks, and the pool can be sized
well below ``slots × max_len`` — more concurrent slots at fixed HBM.

TPU-first shape discipline (vs the GPU original's per-block kernels):

* the pool is one static ``[L, NB, Hkv, P, D]`` buffer; block tables
  are a ``[B, MB]`` int32 array — every shape is fixed at engine
  construction, so decode remains ONE compiled program;
* the pool is never taken apart. The layer scan CARRIES it
  (``forward_paged``), as the chunk's step scan carries the cache, and
  every write and read addresses it by layer index: a plane sliced out
  of ``xs`` and stacked back into ``ys`` was a copy of the plane a
  layer and of the whole pool a step (PR 29: 20.7 of a 28.5 ms step);
* the decode step (S = 1, float pool) WRITES AND READS THROUGH THE
  TABLE, BY LENGTH: ``ops/decode_attention.paged_decode`` is handed the
  whole pools, the layer and the step's new K/V rows, walks each slot's
  blocks by DMA only as far as the slot's length, puts the new row into
  the block of position ``len`` while it holds it and sends that one
  block back, with the einsum path's precision (``_cache_step``;
  ``decode_path`` is the one rule for when). Measured on a v5e at 48
  slots x 2,048 positions, 8 kv heads x 128 (my chip run, PR 31,
  PERF.md §6): 36 us a layer with 3 rows live at ~230 positions, 195 us
  with 20 at ~1,400, against 2.7 ms a layer whatever the slots hold for
  the dense view below, and 63 us a layer for the row scatter alone
  (PR 26 to PR 30 scattered the row first: 18% of the step);
* every other write goes into the pool seen as ``L * NB`` blocks
  (``pool_write``) at ``table[b, len // P]``, offset ``len % P``, as
  ``write_path`` says: a wide window (the shared-prefix prefill's tail,
  S = W) as the WHOLE blocks it touches, gathered, overlaid and
  scattered back; a narrow one (the latent decode step, S = 1; the
  speculative verify, S = k + 1) as ``[D]`` rows, which XLA updates
  one at a time (~70 ns a row of 8 heads x 128: a Llama tail of 256
  was 24 x 2 x 142 us of row updates a prefill, 11% of a saturated
  device, ledger PR 42; 17 us a layer-plane by blocks, PR 45);
* everything else — the speculative verify (S = k + 1), the
  shared-prefix prefill (S = W, one row), int8 pools, backends without
  the kernel — GATHERS each row's whole table out of the same flat view
  (``pool_view``) into the standard ``[B, H, MB·P, D]`` attention view
  and reuses the dense cache's math (``_gather_attention`` ->
  ``generate._cached_attention``). That is all of ``max_len`` for every
  slot, live or not, per layer per step: 70% of the decode step when
  S = 1 still took it (ledger, PR 25);
* unallocated table entries point at block 0, a dedicated JUNK SINK no
  request ever owns: a row that finished mid-chunk keeps decoding
  (static shapes forbid shrinking the batch) and what it writes past
  its reservation lands harmlessly there, as do the scatter's writes of
  inactive rows (the kernel writes none).

Accounting (free list, per-slot block lists) is host-side in the
engine — the device never sees an allocation decision, only tables.
"""
from __future__ import annotations

import collections
import dataclasses
import heapq
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from skypilot_tpu.models import llama
from skypilot_tpu.models.generate import (KVCache, _cached_attention,
                                          _mlp_tail, _qkv_proj,
                                          _quantize_block)
from skypilot_tpu.models.quantization import mm as _mm
from skypilot_tpu.ops import attention as attention_ops
from skypilot_tpu.ops import decode_attention
# Compile ledger (observability/profiler.py): see models/generate.py.
from skypilot_tpu.observability.profiler import profiled_jit
from skypilot_tpu.utils import prefix_affinity as affinity_lib


@dataclasses.dataclass
class PagedKVCache:
    """Block pool + per-slot tables. ``k``/``v``: [L, NB, Hkv, P, D];
    ``tables``: [B, MB] int32 block ids (0 = junk sink / unallocated);
    ``lengths``: [B] tokens cached per slot. INT8 mode adds per-position
    scales [L, NB, Hkv, P] (same recipe as the dense cache). A latent
    (MLA) pool is ONE plane of ``c_kv | k_rope`` rows: ``k``
    [L, NB, 1, P, W] and ``v`` None (models/mla_moe.py); the table, the
    block accounting, the insert and the fork do not care how wide a
    row is or how many planes there are."""
    k: jax.Array
    v: Optional[jax.Array]
    tables: jax.Array
    lengths: jax.Array
    k_s: Optional[jax.Array] = None
    v_s: Optional[jax.Array] = None

    @property
    def quantized(self) -> bool:
        return self.k_s is not None

    @property
    def block(self) -> int:
        return self.k.shape[3]

    @property
    def max_blocks(self) -> int:
        return self.tables.shape[1]


jax.tree_util.register_dataclass(
    PagedKVCache, data_fields=['k', 'v', 'tables', 'lengths', 'k_s',
                               'v_s'], meta_fields=[])


def init_pool(cfg: llama.LlamaConfig, slots: int, max_len: int,
              n_blocks: int, block: int,
              quantize: bool = False, kv_sharding=None,
              scale_sharding=None,
              lengths_sharding=None) -> PagedKVCache:
    """``n_blocks`` INCLUDES block 0 (the junk sink); usable capacity is
    ``(n_blocks - 1) * block`` positions. ``max_blocks`` per slot covers
    ``max_len`` so a single request can still use its full budget.

    Optional shardings allocate the pool BORN sharded for TP serving
    (kv_heads over the tensor axis — the same plane the dense cache
    shards). Block tables stay replicated: every scatter/gather indexes
    the replicated NB/P dims only, so GSPMD partitions the pool ops
    with zero collectives."""
    if block < 1 or block & (block - 1):
        # Prefill widths are power-of-two buckets: a non-power-of-two
        # block could leave w >= block with w % block != 0, and the
        # insert's floor(w / block) scatter would silently DROP the
        # prompt's tail KV (review finding).
        raise ValueError(f'block size must be a power of two, '
                         f'got {block}')
    if max_len % block:
        raise ValueError(f'max_len {max_len} must be a multiple of the '
                         f'block size {block}')
    mb = max_len // block
    shape = (cfg.n_layers, n_blocks, cfg.n_kv_heads, block, cfg.head_dim)
    tables = jnp.zeros((slots, mb), jnp.int32)
    lengths = jnp.zeros((slots,), jnp.int32, device=lengths_sharding)
    if quantize:
        return PagedKVCache(
            k=jnp.zeros(shape, jnp.int8, device=kv_sharding),
            v=jnp.zeros(shape, jnp.int8, device=kv_sharding),
            tables=tables, lengths=lengths,
            k_s=jnp.zeros(shape[:-1], jnp.float32,
                          device=scale_sharding),
            v_s=jnp.zeros(shape[:-1], jnp.float32,
                          device=scale_sharding))
    return PagedKVCache(k=jnp.zeros(shape, cfg.dtype, device=kv_sharding),
                        v=jnp.zeros(shape, cfg.dtype, device=kv_sharding),
                        tables=tables, lengths=lengths)


# ---------------------------------------------------------------------------
# Insert: scatter a dense prefilled cache (models/generate.KVCache, the
# prefill path is unchanged) into pool blocks.


def _insert_impl(pool: PagedKVCache, cache_n, tables_new: jax.Array,
                 slots: jax.Array) -> PagedKVCache:
    """Write dense rows ``cache_n`` [L, N, H, W, D] (W a multiple-of-P
    or < P bucket) into the pool under each row's block table
    ``tables_new`` [N, MB], and install those tables at ``slots``.
    Positions beyond a row's reserved blocks carry junk (never attended)
    and scatter into the junk sink."""
    p = pool.block
    w = cache_n.k.shape[3]

    def scatter(pool_arr, new):  # new: [L, N, H, W, D]
        if w < p:
            blk = tables_new[:, 0]
            return pool_arr.at[:, blk, :, :w].set(new)
        nb = w // p
        # [L, N, H, nb, P, D] -> [L, N*nb, H, P, D] against flat ids.
        l, n, h, _, d = new.shape
        v = new.reshape(l, n, h, nb, p, d).transpose(0, 1, 3, 2, 4, 5)
        v = v.reshape(l, n * nb, h, p, d)
        return pool_arr.at[:, tables_new[:, :nb].reshape(-1)].set(v)

    def scatter_s(pool_s, new_s):  # scales: [L, N, H, W]
        if w < p:
            blk = tables_new[:, 0]
            return pool_s.at[:, blk, :, :w].set(new_s)
        nb = w // p
        l, n, h, _ = new_s.shape
        v = new_s.reshape(l, n, h, nb, p).transpose(0, 1, 3, 2, 4)
        v = v.reshape(l, n * nb, h, p)
        return pool_s.at[:, tables_new[:, :nb].reshape(-1)].set(v)

    k = scatter(pool.k, cache_n.k)
    v = None if pool.v is None else scatter(pool.v, cache_n.v)
    k_s, v_s = pool.k_s, pool.v_s
    if pool.quantized:
        k_s = scatter_s(pool.k_s, cache_n.k_s)
        v_s = scatter_s(pool.v_s, cache_n.v_s)
    return PagedKVCache(
        k=k, v=v, tables=pool.tables.at[slots].set(tables_new),
        lengths=pool.lengths.at[slots].set(cache_n.lengths),
        k_s=k_s, v_s=v_s)


jit_insert = profiled_jit('paged.insert', _insert_impl,
                          donate_argnums=(0,))


# ---------------------------------------------------------------------------
# Decode forwards: write the step's K/V, then attend. S=1, the chunked
# decode step: both in one kernel, through the block table. Everything
# else: the row scatter, then the gathered dense view with the dense
# math. S=k+1 is the speculative VERIFY window (writes span up to two
# blocks per row; rollback afterwards is just a lengths rewind —
# rolled-back block positions are never attended and get overwritten on
# the next write, the same invariant as the dense cache).


def _block_offsets(tables: jax.Array, lengths: jax.Array, s: int,
                   p: int, active_rows) -> Tuple[jax.Array, jax.Array]:
    """Flattened (block ids, in-block offsets) for positions
    [lengths, lengths+S) per row — the ONE definition of the table
    lookup (clip past-table writes to the last entry; divert inactive
    rows to the junk sink) shared by the code and scale planes."""
    mb = tables.shape[1]
    pos = lengths[:, None] + jnp.arange(s, dtype=jnp.int32)[None]  # B,S
    blk = jnp.take_along_axis(
        tables, jnp.clip(pos // p, 0, mb - 1), axis=1)  # [B, S]
    if active_rows is not None:
        blk = jnp.where(active_rows[:, None], blk, 0)
    return blk.reshape(-1), (pos % p).reshape(-1)


def _scatter_rows(pool: jax.Array, tables: jax.Array,
                  lengths: jax.Array, new: jax.Array,
                  active_rows) -> jax.Array:
    """Scatter ``new`` [B, H, S, D] at positions [lengths, lengths+S)
    per row into ``pool`` [NB, H, P, D] under ``tables`` [B, MB], as
    [D] rows into the plane seen as [NB*H*P, D]: the window is the
    plane's minor dim, so XLA keeps the pool in its row-major layout —
    the one a Mosaic call's operands must have. Written as a scatter of
    [H, D] slabs (``pool.at[blk, :, off]``) the TPU compiler re-lays the
    whole pool as [NB, P, H, D] inside the decode loop and converts it
    back in front of the kernel (PR 26: 9 ms of a 38 ms step). XLA
    updates one [D] row at a time, ~70-120 ns a row on a v5e: the
    narrow windows' write (``write_path``); a wide one goes by
    blocks."""
    b, h, s, d = new.shape
    nb, _, p, _ = pool.shape
    blk, off = _block_offsets(tables, lengths, s, p, active_rows)
    rows = ((blk[:, None] * h + jnp.arange(h, dtype=jnp.int32)[None]) * p
            + off[:, None]).reshape(-1)
    vals = new.transpose(0, 2, 1, 3).reshape(b * s * h, d)
    return pool.reshape(nb * h * p, d).at[rows].set(vals).reshape(
        pool.shape)


def _write_blocks(pool: jax.Array, tables: jax.Array,
                  lengths: jax.Array, new: jax.Array,
                  active_rows) -> jax.Array:
    """``_scatter_rows``' write by whole blocks: the ``nbt = ceil(S/P)
    + 1`` blocks that positions [lengths, lengths + S) can touch are
    gathered out of ``pool`` [NB, H, P, D] as slabs on its leading dim
    (a static count), the window goes in at in-block offset
    ``lengths % P`` of their [H, nbt·P, D] view, and they are scattered
    back whole, so each touched block keeps the positions outside the
    window as it read them (a shared or forked prefix, byte for byte).
    A block the window does not reach, one past the table (never
    clipped onto the table's last entry: two updates of one live block,
    one stale, would race) and every block of an inactive row go to the
    junk sink (block 0) instead, like the writes past a reservation."""
    b, h, s, d = new.shape
    p = pool.shape[2]
    mb = tables.shape[1]
    nbt = -(-s // p) + 1
    j = lengths[:, None] // p + jnp.arange(nbt, dtype=jnp.int32)[None]
    live = (j < mb) & (j * p < (lengths + s)[:, None])
    if active_rows is not None:
        live = live & active_rows[:, None]
    ids = jnp.where(live, jnp.take_along_axis(
        tables, jnp.minimum(j, mb - 1), axis=1), 0).reshape(-1)
    view = pool[ids].reshape(b, nbt, h, p, d).transpose(0, 2, 1, 3, 4)
    view = jax.vmap(lambda v, n, o: jax.lax.dynamic_update_slice(
        v, n, (0, o, 0)))(view.reshape(b, h, nbt * p, d), new,
                          lengths % p)
    blocks = view.reshape(b, h, nbt, p, d).transpose(0, 2, 1, 3, 4)
    return pool.at[ids].set(blocks.reshape((b * nbt,) + pool.shape[1:]))


def _scatter_multi_s(pool_s: jax.Array, tables: jax.Array,
                     lengths: jax.Array, new_s: jax.Array,
                     active_rows) -> jax.Array:
    """[B, H, S] scale-plane counterpart of ``_scatter_rows`` ([H]
    slabs: a scale plane feeds no Mosaic call)."""
    b, h, s = new_s.shape
    blk, off = _block_offsets(tables, lengths, s, pool_s.shape[2],
                              active_rows)
    vals = new_s.transpose(0, 2, 1).reshape(b * s, h)
    return pool_s.at[blk, :, off].set(vals)


def _layer_blocks(pool: jax.Array, l, tables: jax.Array):
    """The pool [L, NB, ...] seen as one plane of L * NB blocks
    (row-major, so the view is free) and ``tables`` moved to layer
    ``l``'s blocks of it. A write through it updates the carried pool
    in place and a read touches the named blocks only, where ``pool[l]``
    is a copy of the plane. Inactive rows still land in block 0 (layer
    0's junk sink)."""
    return pool.reshape((-1,) + pool.shape[2:]), tables + l * pool.shape[1]


def write_path(s: int, p: int, h: int) -> str:
    """How ``pool_write`` puts a window of ``s`` positions a row into a
    plane of ``h`` heads (as the writing program holds it: a TP shard's
    heads) in blocks of ``p`` positions: ``'blocks'`` (``_write_blocks``:
    the touched blocks gathered whole, the window laid in, the blocks
    scattered back) or ``'rows'`` (``_scatter_rows``: one [D] row a
    position and head). XLA moves either one index at a time, ~70-120
    ns a [D] row, ~0.4-1.7 us a whole block beside ~3.5 us for the
    three operations (a v5e, alone, PERF.md section 6, PR 45), so
    blocks win once the window's ``h * s`` rows number four blocks'
    positions: every shared-prefix prefill's tail at 8 heads (the
    Llama cells, from S = 16), a latent tail of 64 or more; the decode
    step (S = 1) and a verify (S = k + 1) keep rows. Decided on static
    shapes, for any number of rows a batch. The ONE definition:
    ``pool_write`` branches on it and the engine counts it
    (``stats()['pool_write']``)."""
    return 'blocks' if h * s >= 4 * p else 'rows'


def pool_write(pool: jax.Array, l, tables: jax.Array, lengths: jax.Array,
               new: jax.Array, active_rows) -> jax.Array:
    """Write ``new`` [B, H, S, D] (a scale plane's: [B, H, S]) of layer
    ``l`` (a traced scalar) at positions [lengths, lengths + S) through
    the tables, by blocks or by rows as ``write_path`` says (a scale
    plane by rows)."""
    flat, blocks = _layer_blocks(pool, l, tables)
    if new.ndim == 3:
        write = _scatter_multi_s
    elif write_path(new.shape[2], pool.shape[3], new.shape[1]) == 'blocks':
        write = _write_blocks
    else:
        write = _scatter_rows
    return write(flat, blocks, lengths, new.astype(pool.dtype),
                 active_rows).reshape(pool.shape)


def pool_view(pool: jax.Array, l, tables: jax.Array) -> jax.Array:
    """Every row's whole table out of layer ``l``: [B, MB, H, P(, D)]."""
    flat, blocks = _layer_blocks(pool, l, tables)
    return flat[blocks]


def decode_path(tables_shape, plane_shape, dtype, quantized: bool) -> str:
    """Which attention the S = 1 step takes over a pool with block
    tables ``tables_shape`` [B, MB] and layer planes ``plane_shape``
    [..., P, D]: ``'paged_kernel'`` (``ops/decode_attention.paged_decode``)
    or ``'gather'`` (the dense view + ``_cached_attention``). Decided on
    what the program can see when it is built: the backend rule of the
    training kernel (a TPU; the interpreter only where a test asks for
    it by name) and the pool's geometry. A pool the kernel cannot take
    on a backend that has the kernel is said once per shape. The ONE
    definition: ``_cache_step`` branches on it and the engine reports
    it (``stats()['decode_attention']``)."""
    if not (attention_ops._use_pallas()
            or decode_attention.PAGED_INTERPRET):
        return 'gather'
    (b, mb), (p, d) = tables_shape, plane_shape[-2:]
    if quantized:
        why = 'int8 pool: the kernel folds no scales'
    elif not decode_attention.paged_fits(b, mb, p, d, dtype):
        why = f'B={b}, MB={mb}, P={p}, D={d} outside paged_fits()'
    else:
        return 'paged_kernel'
    attention_ops.log_fallback_once('paged_decode', (b, mb, p, d), why)
    return 'gather'


def _gather_attention(q, k_pool, v_pool, k_s, v_s, l, tables,
                      lengths) -> jax.Array:
    """Attention of q [B, S, Hq, D] at positions [lengths, lengths + S)
    over a dense view: every row's whole table gathered out of layer
    ``l``, [B, MB, H, P, D] -> [B, H, MB*P, D], then the dense cache's
    math. What S > 1 (speculative verify, shared-prefix prefill), int8
    pools and backends without the kernel take."""
    s = q.shape[1]

    def view(pool):
        if pool is None:
            return None
        g = jnp.moveaxis(pool_view(pool, l, tables), 2, 1)  # B,H,MB,P..
        return g.reshape(g.shape[:2] + (-1,) + g.shape[4:])

    positions = lengths[:, None] + jnp.arange(s, dtype=jnp.int32)[None]
    return _cached_attention(q, view(k_pool), view(v_pool), positions,
                             lengths + s, view(k_s), view(v_s))


def _cache_step(q: jax.Array, kt: jax.Array, vt: jax.Array, pools, l,
                tables: jax.Array, lengths: jax.Array, active_rows,
                shard_ctx):
    """One layer's cache write and read: q [B, S, Hq, D] and this
    step's kt/vt [B, Hkv, S, D] against layer ``l`` of the WHOLE pools
    ``(k, v, k_s, v_s)`` ([L, NB, Hkv, P, D]; the scale planes
    [L, NB, Hkv, P] or None). Where ``decode_path`` says so (S = 1, a
    float pool the kernel takes), ``paged_decode`` does both through
    the block table: the row goes into the block of position
    ``lengths`` that the kernel holds anyway, and the slot attends
    positions <= ``lengths`` (inactive rows, valid 0, read and write
    nothing: their output is never used and their stale tables may name
    blocks that now belong to another request). Everything else
    scatters kt/vt (an int8 pool: their codes and scales) at
    [lengths, lengths + S), inactive rows into the junk sink, then
    attends over the gathered view.
    -> (att [B, S, Hq, D], pools)."""
    kernel = q.shape[1] == 1 and decode_path(
        tables.shape, pools[0].shape, pools[0].dtype,
        pools[2] is not None) == 'paged_kernel'

    def step(q, kt, vt, pools, l, tables, lengths, active):
        k_pool, v_pool, k_s, v_s = pools
        if kernel:
            valid = lengths + 1
            if active is not None:
                valid = jnp.where(active, valid, 0)
            att, k_pool, v_pool = decode_attention.paged_decode(
                q[:, 0], kt[:, :, 0], vt[:, :, 0], k_pool, v_pool, l,
                tables, valid, interpret=not attention_ops._use_pallas())
            return att[:, None], (k_pool, v_pool, k_s, v_s)
        if k_s is not None:
            (kt, ks_new), (vt, vs_new) = (_quantize_block(kt),
                                          _quantize_block(vt))
            k_s = pool_write(k_s, l, tables, lengths, ks_new, active)
            v_s = pool_write(v_s, l, tables, lengths, vs_new, active)
        k_pool = pool_write(k_pool, l, tables, lengths, kt, active)
        v_pool = pool_write(v_pool, l, tables, lengths, vt, active)
        att = _gather_attention(q, k_pool, v_pool, k_s, v_s, l, tables,
                                lengths)
        return att, (k_pool, v_pool, k_s, v_s)

    args = (q, kt, vt, pools, l, tables, lengths, active_rows)
    if shard_ctx is None:
        return step(*args)
    # TP serving: write and read per kv-head shard (heads are
    # independent; GSPMD cannot partition a Mosaic call, nor the row
    # scatter's reshape over the sharded head dim: either would gather
    # the pool). The layer, tables, lengths and every batch dim
    # replicated: a table indexes the whole pool. check_vma off: the
    # kernel's scalar-prefetch grid confuses the replication checker.
    mesh, p_q, p_kv = shard_ctx
    spec = jax.sharding.PartitionSpec
    heads, new = spec(None, None, p_q[1], None), spec(None, p_kv[1])
    pool = spec(None, None, p_kv[1])
    return jax.shard_map(
        step, mesh=mesh,
        in_specs=(heads, new, new, pool, spec(), spec(), spec(), spec()),
        out_specs=(heads, pool), check_vma=False)(*args)


def _paged_layer(cfg: llama.LlamaConfig, x: jax.Array, layer, l,
                 lengths: jax.Array, tables: jax.Array, pools,
                 active_rows: Optional[jax.Array], shard_ctx=None):
    """One decoder block at S>=1 over the paged pool. x: [B, S, d]
    (S=1 decode step; S=k+1 speculative verify); ``pools`` the whole
    ``(k, v, k_s, v_s)`` of every layer, of which this is layer ``l``.
    The math is generate.py's (_qkv_proj/_cached_attention/_mlp_tail);
    only the cache write and read differ from the dense layer
    (``_cache_step``): both through the table in the kernel, or the pool
    scatter and the block gather. On the scatter path (S > 1, int8
    pools, no kernel) INACTIVE rows scatter to the junk sink (block 0)
    unconditionally: a freed slot's stale table may point at blocks
    already reallocated to another request, and an unmasked junk write
    there would corrupt the new owner's live KV; the kernel writes
    nothing for them. Within a chunk a finishing row stays active and
    its blocks are only released after the chunk returns, so active
    writes never race a reallocation."""
    b, s = x.shape[0], x.shape[1]
    positions = (lengths[:, None]
                 + jnp.arange(s, dtype=jnp.int32)[None])  # [B, S]
    q, k, v = _qkv_proj(cfg, x, layer, positions)
    att, pools = _cache_step(
        q, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3), pools, l,
        tables, lengths, active_rows, shard_ctx)
    x = x + _mm(att, layer['wo'], 'bshk,hkd->bsd')
    token_mask = None
    if cfg.num_experts > 0:
        mask = jnp.ones((b, s), bool)
        if active_rows is not None:
            mask = mask & active_rows[:, None]
        token_mask = mask.astype(x.dtype)
    x = _mlp_tail(cfg, x, layer, token_mask)
    return x, pools


def forward_paged(params, tokens: jax.Array, cache: PagedKVCache,
                  cfg: llama.LlamaConfig,
                  active_rows: Optional[jax.Array] = None,
                  shard_ctx=None,
                  all_logits: bool = False,
                  logit_index: Optional[jax.Array] = None
                  ) -> Tuple[jax.Array, PagedKVCache]:
    """Run ``tokens`` [B, S] over the paged pool (S=1 decode step;
    S=k+1 speculative verify; S=W padded tail prefill); returns
    (logits, cache advanced S). ``all_logits`` returns per-POSITION
    logits [B, S, V] (the verify needs the target's prediction after
    every proposed token); ``logit_index`` [B] instead picks each row's
    own last REAL position (padded prefill). The structural twin of
    ``generate.forward_cached`` with pool scatter/gather replacing the
    dense row update; the pools ride the layer scan as a CARRY, whole
    (see the module header), for every S and pool kind."""
    x = params['embed'].astype(cfg.dtype)[tokens]
    s = tokens.shape[1]

    def body(carry, step):
        x, pools = carry
        layer, l = step
        return _paged_layer(cfg, x, layer, l, cache.lengths, cache.tables,
                            pools, active_rows, shard_ctx), None

    (x, (new_k, new_v, new_ks, new_vs)), _ = jax.lax.scan(
        body, (x, (cache.k, cache.v, cache.k_s, cache.v_s)),
        (params['layers'], jnp.arange(cfg.n_layers, dtype=jnp.int32)))
    x = llama.rms_norm(x, params['final_norm'], cfg.norm_eps)
    new_cache = PagedKVCache(k=new_k, v=new_v, tables=cache.tables,
                             lengths=cache.lengths + s,
                             k_s=new_ks, v_s=new_vs)
    if all_logits:
        return (_mm(x, params['lm_head'], 'bsd,dv->bsv',
                    preferred_element_type=jnp.float32), new_cache)
    if logit_index is not None:
        # Padded multi-token prefill: each row's logits come from its own
        # last REAL position, not the padded tail (forward_cached's
        # row_lens - 1 trick, against the paged pool).
        last = jnp.take_along_axis(
            x, logit_index[:, None, None].astype(jnp.int32), axis=1)[:, 0]
        return (_mm(last, params['lm_head'], 'bd,dv->bv',
                    preferred_element_type=jnp.float32), new_cache)
    logits = _mm(x[:, -1], params['lm_head'], 'bd,dv->bv',
                 preferred_element_type=jnp.float32)
    return logits, new_cache


# ---------------------------------------------------------------------------
# Copy-on-write block-level prefix sharing (vLLM/SGLang-style).
#
# The pool's block tables make prefix reuse a TABLE WRITE instead of a
# KV copy: committed full token blocks are indexed host-side in a trie
# keyed by token-block chains (exact-match — no hash collisions), with
# per-block refcounts. A matching request points its table head at the
# shared blocks and prefills only its unshared tail DIRECTLY over the
# pool (``jit_prefill_shared``); a partially-matched tail block is
# copy-on-write-forked (``jit_fork_block``) before the first divergent
# append. Eviction is refcount-aware LRU over idle (refs == 0) blocks.
# All BlockTrie methods assume the caller holds the engine lock.


class _TrieNode:
    """One committed full KV block. ``key`` is the block's token tuple;
    ``children`` chain deeper blocks of the same prefix. ``detached``
    marks a node whose ancestor was evicted: it can never be matched
    again, so when its refs drop to zero its block frees directly
    instead of parking in the idle LRU. ``chain`` is the digest of the
    whole token chain root->here (utils/prefix_affinity.py) — a pure
    function of the tokens, so it is stable across commit/evict cycles
    and across replicas; ``hits``/``hit_tick`` carry a DECAYED match
    count (the hotness signal summary truncation orders by — see
    ``BlockTrie._hotness``)."""
    __slots__ = ('block', 'key', 'parent', 'children', 'refs', 'detached',
                 'chain', 'hits', 'hit_tick')

    def __init__(self, block: int, key: tuple,
                 parent: Optional['_TrieNode']):
        self.block = block
        self.key = key
        self.parent = parent
        self.children: Dict[tuple, '_TrieNode'] = {}
        self.refs = 1
        self.detached = False
        self.chain = affinity_lib.chain_digest(
            parent.chain if parent is not None else None, key)
        self.hits = 0.0
        self.hit_tick = 0


class BlockTrie:
    """Host-side index of committed prefix blocks. Pure bookkeeping —
    the device only ever sees block ids via tables. Invariant: every
    block the trie holds is either ``referenced`` (refs > 0, pinned by
    at least one live slot) or in the ``idle`` LRU (refs == 0,
    reclaimable); ``reclaimable`` is exact because eviction cascades
    over a popped node's whole idle subtree."""

    # Hotness half-life in MATCH EVENTS (not wall time — deterministic
    # and replay-safe): a chain unmatched for this many trie matches
    # counts half its hits, so a historically hot tenant that left
    # cannot squat the bounded summary() advert forever against live
    # traffic.
    HITS_HALF_LIFE = 512

    def __init__(self, block: int):
        self.block = block
        self.children: Dict[tuple, _TrieNode] = {}
        self.idle: 'collections.OrderedDict[_TrieNode, None]' = \
            collections.OrderedDict()
        self.referenced = 0  # nodes with refs > 0 (incl. detached)
        self._match_tick = 0  # total match() calls; the decay clock

    @property
    def reclaimable(self) -> int:
        return len(self.idle)

    @property
    def blocks_held(self) -> int:
        return self.referenced + len(self.idle)

    def match(self, row: List[int],
              limit: Optional[int] = None
              ) -> Tuple[List[_TrieNode], Optional[_TrieNode], int]:
        """Longest committed chain covering ``row`` at block
        granularity, capped at ``limit`` tokens (default ``len(row) - 1``
        — the last prompt token must be computed to produce the first
        logits). Returns (full-block nodes, partial-tail node, partial
        length): the partial node is a committed child whose token
        tuple extends the row past the full matches by 1..block-1
        tokens — the copy-on-write fork candidate."""
        limit = len(row) - 1 if limit is None else limit
        p = self.block
        self._match_tick += 1
        nodes: List[_TrieNode] = []
        kids = self.children
        pos = 0
        while pos + p <= limit:
            node = kids.get(tuple(row[pos:pos + p]))
            if node is None:
                break
            # Hotness for summary() truncation order: decay-then-bump.
            node.hits = self._hotness(node) + 1.0
            node.hit_tick = self._match_tick
            nodes.append(node)
            pos += p
            kids = node.children
        partial, plen = None, 0
        rest = row[pos:limit]
        if rest:
            for key, node in kids.items():
                m = 0
                for a, b in zip(key, rest):
                    if a != b:
                        break
                    m += 1
                if m > plen:
                    partial, plen = node, m
        return nodes, partial, plen

    def acquire(self, node: _TrieNode) -> None:
        if node.refs == 0:
            self.referenced += 1
            self.idle.pop(node, None)
        node.refs += 1

    def release(self, node: _TrieNode) -> Optional[int]:
        """Decref; returns the node's block id when it must be FREED
        now (a detached node dying), else None (live nodes park in the
        idle LRU as reusable cache)."""
        node.refs -= 1
        if node.refs > 0:
            return None
        self.referenced -= 1
        if node.detached:
            return node.block
        self.idle[node] = None  # newest end of the LRU
        return None

    def touch(self, node: _TrieNode) -> None:
        if node in self.idle:
            self.idle.move_to_end(node)

    def commit(self, parent: Optional[_TrieNode], key: tuple,
               block: int) -> Optional[_TrieNode]:
        """Attach ``block`` as a committed child of ``parent`` (None =
        root). Returns the new node (born with refs=1, held by the
        committing slot), or None when an identical-content child
        already exists — the caller keeps ownership of its duplicate
        and chains deeper commits under the existing node."""
        kids = parent.children if parent is not None else self.children
        if key in kids:
            return None
        node = _TrieNode(block, key, parent)
        kids[key] = node
        self.referenced += 1
        return node

    def child(self, parent: Optional[_TrieNode],
              key: tuple) -> Optional[_TrieNode]:
        kids = parent.children if parent is not None else self.children
        return kids.get(key)

    def _hotness(self, node: _TrieNode) -> float:
        """Match count decayed by match events since the node's last
        hit (half-life ``HITS_HALF_LIFE``) — the advert ordering
        signal. Event-based, so it is deterministic and idle trees do
        not decay."""
        if node.hits <= 0.0:
            return 0.0
        age = self._match_tick - node.hit_tick
        return node.hits * 0.5 ** (age / self.HITS_HALF_LIFE)

    def summary(self, max_entries: int = 64) -> dict:
        """Compact resident-chain advert for fleet prefix-affinity
        routing (utils/prefix_affinity.py): up to ``max_entries``
        ``[chain_hex, depth]`` pairs plus pool-level counts, shipped in
        the replica's /health body. HARD payload bound: entries are
        truncated hottest-first (decayed match count — see
        ``_hotness``), then deepest-first, then by chain digest — a
        deterministic order, so two identically-warmed replicas
        advertise identical summaries. Detached nodes are excluded
        (they can never match again); hashes are pure functions of the
        token chain, so a chain evicted and re-committed keeps its
        hash. Called under the engine lock on every /health: bounded
        heap selection (O(n log k)), and only the kept entries pay the
        hex conversion."""
        items = []  # (-hotness, -depth, chain_bytes)
        total = 0
        stack = [(node, 1) for node in self.children.values()]
        while stack:
            node, depth = stack.pop()
            total += 1
            if not node.detached:
                items.append((-self._hotness(node), -depth, node.chain))
            stack.extend((ch, depth + 1)
                         for ch in node.children.values())
        kept = heapq.nsmallest(max(int(max_entries), 0), items)
        return {'v': affinity_lib.SUMMARY_VERSION, 'block': self.block,
                'nodes': total, 'resident': self.blocks_held,
                'truncated': len(items) > len(kept),
                'entries': [[c.hex(), -d] for (_, d, c) in kept]}

    def resolve_chains(self, digests: List[bytes]) -> Dict[bytes, List[int]]:
        """Map advert chain digests back to the token chains this trie
        holds — the migration pre-warm answer (serve/remediation.py):
        the advert carries only ``chain_digest`` values, but the OWNING
        replica can reconstruct each digest's full token prefix by
        walking parents root-ward. Detached nodes are excluded (their
        blocks are mid-handoff and may vanish). Caller holds the engine
        lock."""
        want = set(digests)
        out: Dict[bytes, List[int]] = {}
        stack = list(self.children.values())
        while stack and want:
            node = stack.pop()
            stack.extend(node.children.values())
            if node.detached or node.chain not in want:
                continue
            want.discard(node.chain)
            parts = []
            cur: Optional[_TrieNode] = node
            while cur is not None:
                parts.append(cur.key)
                cur = cur.parent
            row: List[int] = []
            for key in reversed(parts):
                row.extend(key)
            out[node.chain] = row
        return out

    def evict(self, n: int) -> List[int]:
        """Reclaim >= n blocks from the idle LRU (may free more: a
        popped node's unreachable idle descendants free with it).
        Returns the freed block ids."""
        return [b for b, _ in self.evict_nodes(n)]

    def evict_nodes(self, n: int) -> List[Tuple[int, _TrieNode]]:
        """Like :meth:`evict` but returns ``(block, node)`` pairs.
        Detached nodes keep ``key``/``parent``/``chain``, so a tiering
        layer (serve/kv_tiers.py) can rebuild each evicted chain's
        token row by walking parents root-ward and DEMOTE the block's
        KV instead of discarding it — the caller must capture (gather)
        the blocks before the freed ids are rescattered."""
        freed: List[Tuple[int, _TrieNode]] = []
        while self.idle and len(freed) < n:
            node, _ = self.idle.popitem(last=False)
            freed.extend(self._detach(node))
        return freed

    def _detach(self, node: _TrieNode) -> List[Tuple[int, _TrieNode]]:
        kids = (node.parent.children if node.parent is not None
                else self.children)
        kids.pop(node.key, None)
        freed = [(node.block, node)]
        stack = list(node.children.values())
        node.children = {}
        while stack:
            ch = stack.pop()
            stack.extend(ch.children.values())
            if ch.refs == 0:
                # Reachable refs-0 nodes are in the idle LRU by
                # construction; unreachable ones free with the subtree.
                self.idle.pop(ch, None)
                freed.append((ch.block, ch))
            else:
                ch.detached = True  # frees at its final release()
        return freed


def _fork_block_impl(pool: PagedKVCache, src: jax.Array,
                     dst: jax.Array) -> PagedKVCache:
    """Copy-on-write fork: duplicate block ``src`` into owned block
    ``dst`` (all planes, all positions — positions past the shared
    partial length are overwritten by the tail prefill / decode writes
    and never attended before that)."""
    k = pool.k.at[:, dst].set(pool.k[:, src])
    v = None if pool.v is None else pool.v.at[:, dst].set(pool.v[:, src])
    k_s, v_s = pool.k_s, pool.v_s
    if pool.quantized:
        k_s = k_s.at[:, dst].set(k_s[:, src])
        v_s = v_s.at[:, dst].set(v_s[:, src])
    return PagedKVCache(k=k, v=v, tables=pool.tables,
                        lengths=pool.lengths, k_s=k_s, v_s=v_s)


jit_fork_block = profiled_jit('paged.fork_block', _fork_block_impl,
                              donate_argnums=(0,))


def _gather_blocks_impl(pool: PagedKVCache,
                        blocks: jax.Array,
                        p_len: jax.Array) -> KVCache:
    """Assemble shared blocks into a DENSE 1-row prefill cache (the
    chunked long-prefill path seeds its scratch row from the trie this
    way). ``blocks`` is a full [MB] table row padded with junk-sink 0s,
    so the gather compiles ONCE (width is always MB*P = max_len);
    ``p_len`` [1] marks the valid shared-prefix tokens — sink junk
    beyond it is never attended."""
    def view(arr):  # [L, NB, H, P, D] -> [L, 1, H, MB*P, D]
        g = arr[:, blocks].transpose(0, 2, 1, 3, 4)
        l, h, mb, p, d = g.shape
        return g.reshape(l, 1, h, mb * p, d)

    ks = vs = None
    if pool.quantized:
        def view_s(arr):
            g = arr[:, blocks].transpose(0, 2, 1, 3)
            l, h, mb, p = g.shape
            return g.reshape(l, 1, h, mb * p)
        ks, vs = view_s(pool.k_s), view_s(pool.v_s)
    return KVCache(k=view(pool.k), v=view(pool.v), lengths=p_len,
                   k_s=ks, v_s=vs)


jit_gather_blocks = profiled_jit('paged.gather_blocks',
                                 _gather_blocks_impl)


# ---------------------------------------------------------------------------
# Disaggregated prefill/decode KV handoff (serve/disagg.py): a
# prefill-role engine exports a prompt's committed blocks in POOL
# LAYOUT — [L, NB, Hkv, P, D], the exact on-device arrangement — so the
# same-host staging path needs zero re-layout and the decode-role
# import is one scatter. Block counts are padded to a power of two by
# the caller (junk-sink ids), bounding compiles at log2(max_blocks)
# per direction.


def _export_blocks_impl(pool: PagedKVCache, blocks: jax.Array):
    """Gather ``blocks`` [NB] (junk-sink-0-padded) out of the pool,
    keeping the block layout. Returns (k, v, k_s, v_s) with the scale
    planes None for bf16 pools (None is a pytree leaf-less node, so
    the two variants trace separately)."""
    k = pool.k[:, blocks]
    v = pool.v[:, blocks]
    if pool.quantized:
        return k, v, pool.k_s[:, blocks], pool.v_s[:, blocks]
    return k, v, None, None


jit_export_blocks = profiled_jit('paged.export_blocks',
                                 _export_blocks_impl)


def _import_blocks_impl(pool: PagedKVCache, k_new, v_new, k_s_new,
                        v_s_new, blocks: jax.Array,
                        table_row: jax.Array, slot: jax.Array,
                        length: jax.Array) -> PagedKVCache:
    """Scatter imported block data [L, NB, H, P, D] into the pool at
    ``blocks`` [NB] and install ``table_row`` [MB] + ``length`` at
    ``slot`` in the SAME dispatch — the decode-role admission is one
    program. Padding entries point at the junk sink (block 0), so a
    zero-block install (full local prefix share) reuses this path with
    an all-sink scatter."""
    k = pool.k.at[:, blocks].set(k_new)
    v = pool.v.at[:, blocks].set(v_new)
    k_s, v_s = pool.k_s, pool.v_s
    if k_s_new is not None:
        k_s = k_s.at[:, blocks].set(k_s_new)
        v_s = v_s.at[:, blocks].set(v_s_new)
    return PagedKVCache(
        k=k, v=v, tables=pool.tables.at[slot].set(table_row),
        lengths=pool.lengths.at[slot].set(length), k_s=k_s, v_s=v_s)


jit_import_blocks = profiled_jit('paged.import_blocks',
                                 _import_blocks_impl,
                                 donate_argnums=(0,))


def _prefill_shared_impl(cfg: llama.LlamaConfig, params,
                         cache: PagedKVCache, tokens: jax.Array,
                         table_row: jax.Array, slot: jax.Array,
                         start: jax.Array, slen: jax.Array,
                         shard_ctx=None) -> Tuple[jax.Array, PagedKVCache]:
    """Suffix prefill DIRECTLY over the pool — the block-share hit
    path. ``tokens`` [1, W] is the padded unshared tail; ``table_row``
    [1, MB] already points its head at the shared blocks and its tail
    at freshly owned ones; ``start`` [1] is the shared token count and
    ``slen`` [1] the real tail length. The forward reads the shared
    prefix through the block gather (the same read decode pays) and
    scatters tail KV straight into the owned blocks — no dense scratch
    row, no insert copy. Installs the table and final length at
    ``slot`` and returns the tail's last-real-token logits."""
    row_cache = PagedKVCache(k=cache.k, v=cache.v, tables=table_row,
                             lengths=start, k_s=cache.k_s, v_s=cache.v_s)
    logits, row_cache = forward_paged(params, tokens, row_cache, cfg,
                                      shard_ctx=shard_ctx,
                                      logit_index=slen - 1)
    tables = cache.tables.at[slot].set(table_row[0])
    lengths = cache.lengths.at[slot].set(start[0] + slen[0])
    return logits, PagedKVCache(k=row_cache.k, v=row_cache.v,
                                tables=tables, lengths=lengths,
                                k_s=row_cache.k_s, v_s=row_cache.v_s)


jit_prefill_shared = profiled_jit('paged.prefill_shared',
                                  _prefill_shared_impl,
                                  static_argnums=(0, 8),
                                  donate_argnums=(2,))
