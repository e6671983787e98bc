"""The one place the serving code asks "which model is this?".

``generate.py``, ``paged.py`` and ``engine.py`` reach a model through
``ops_for(cfg)``: a small table keyed on the config's TYPE that names
the functions building and driving its caches, and says which engine
features compose with it. No ``isinstance`` / ``num_experts`` tests in
the loop: a new architecture is a new row here (and its own module),
not a new branch there.

The Llama row names exactly the functions the engine called before the
table existed (its programs are unchanged); it is built lazily because
those functions live in the modules that import this one.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional


@dataclasses.dataclass(frozen=True)
class ModelOps:
    """What the serving code needs of a model family.

    Caches: ``init_cache`` (dense, [B, width] rows: prefill scratch),
    ``init_pool`` (the engine's paged pool). Programs, all jitted:
    ``prefill(params, tokens, cache, cfg, row_lens) -> (logits, cache)``;
    ``insert_paged(pool, cache_n, tables, slots) -> pool``;
    ``fork_block(pool, src, dst) -> pool``;
    ``prefill_shared(cfg, params, pool, tokens, table_row, slot, start,
    slen, shard_ctx) -> (logits, pool)``;
    ``paged_chunk(cfg, k, params, pool, last, temps, top_ks, top_ps,
    active, key, shard_ctx) -> (pool, last, toks, counts)`` where
    ``counts`` is None or the experts' token counts [E] of the chunk;
    ``paged_chunk_n``: None, or ``paged_chunk`` with ``n_steps`` (a
    scalar <= k) in place of ``shard_ctx``: the chunk stops there;
    ``forward_cached``: the un-jitted dense forward (``generate``'s
    window path scans it).

    ``decode_attention(pool, quantized) -> str``: how the S = 1 step
    reads the pool (``stats()['decode_attention']``).
    ``step_paths(pool) -> {stats key: answer}``: the family's further
    rules for its decode program, each reported under its own key (a
    recurrent state: how its one-token step runs).
    ``state_bytes_per_slot(cfg)``: what a sequence keeps beside its
    paged rows, whatever its length (a recurrent state a slot).
    ``prefill_chunk(cfg)``: the family's default piece of a chunked
    long prefill (0: none).
    ``rows_couple(cfg)``: True where co-batched rows influence each
    other (capacity-dropping experts): pipelining, chunked prefill,
    block sharing, speculation and KV handoff all need independent
    rows. ``refuses``: engine features this family does not
    implement, each with the reason its error gives."""
    name: str
    init_cache: Callable
    init_pool: Callable
    prefill: Callable
    forward_cached: Callable
    insert_paged: Callable
    fork_block: Callable
    prefill_shared: Callable
    paged_chunk: Callable
    decode_attention: Callable
    kv_bytes_per_token: Callable[[Any], int]
    rows_couple: Callable[[Any], bool]
    # Bytes a SEQUENCE costs the cache whatever its length (a recurrent
    # state a slot, beside what a token costs): 0 where a sequence is
    # its keys and values alone.
    state_bytes_per_slot: Callable[[Any], int] = lambda cfg: 0
    step_paths: Callable[[Any], Dict[str, str]] = lambda pool: {}
    # The piece (tokens) a long prompt's prefill advances by between
    # decode chunks where neither the caller nor the environment says
    # (``ContinuousEngine(prefill_chunk=)``): 0, the whole prompt in
    # one group prefill, unless the family names a piece of its own.
    prefill_chunk: Callable[[Any], int] = lambda cfg: 0
    paged_chunk_n: Optional[Callable] = None
    refuses: Dict[str, str] = dataclasses.field(default_factory=dict)

    def refuse(self, feature: str) -> None:
        """Raise the family's reason if it refuses ``feature``."""
        why = self.refuses.get(feature)
        if why is not None:
            raise ValueError(f'{feature} is not available for a '
                             f'{self.name} model: {why}')


def _llama() -> ModelOps:
    from skypilot_tpu.models import engine, generate, paged

    def paged_chunk(*args):
        return (*engine._jit_paged_chunk(*args), None)

    def kv_bytes(cfg) -> int:
        import jax.numpy as jnp
        return (2 * cfg.n_layers * cfg.n_kv_heads * cfg.head_dim
                * jnp.dtype(cfg.dtype).itemsize)

    return ModelOps(
        name='llama',
        init_cache=generate.init_cache, init_pool=paged.init_pool,
        prefill=generate._jit_prefill,
        forward_cached=generate.forward_cached,
        insert_paged=paged.jit_insert, fork_block=paged.jit_fork_block,
        prefill_shared=paged.jit_prefill_shared, paged_chunk=paged_chunk,
        decode_attention=lambda pool, quantized: paged.decode_path(
            pool.tables.shape, pool.k.shape, pool.k.dtype, quantized),
        kv_bytes_per_token=kv_bytes,
        # The capacity path (models/moe.moe_mlp): expert capacity is per
        # forward CALL, so a row's routing depends on its batchmates.
        rows_couple=lambda cfg: cfg.num_experts > 0)


def _mla_moe() -> ModelOps:
    from skypilot_tpu.models import mla_moe, paged
    one_plane = ('the latent cache is one plane of c_kv | k_rope rows; '
                 'this path moves K and V planes')
    return ModelOps(
        name='mla_moe',
        init_cache=mla_moe.init_cache, init_pool=mla_moe.init_pool,
        prefill=mla_moe.jit_prefill,
        forward_cached=mla_moe.forward_cached,
        insert_paged=paged.jit_insert, fork_block=paged.jit_fork_block,
        prefill_shared=mla_moe.jit_prefill_shared,
        paged_chunk=mla_moe.jit_paged_chunk,
        decode_attention=lambda pool, quantized: mla_moe.decode_path(
            pool.tables.shape, pool.k.shape, pool.k.dtype),
        kv_bytes_per_token=lambda cfg: cfg.kv_bytes_per_token,
        rows_couple=lambda cfg: False,     # drop-free routing
        refuses={
            'kv_quantize': 'the latent pool has no int8 mode',
            'kv_tiers': one_plane,
            'KV handoff': one_plane,
            'speculative decoding': 'no S = k + 1 verify over the latent '
                                    'pool',
            'prefill_chunk': 'the chunked long prefill seeds a dense K/V '
                             'scratch row',
            'tensor parallelism': 'the latent plane has one head: no '
                                  'head-sharded pool or kernel'})


# Eight of ``kda.CHUNK``. On a v5e at Kimi-Linear's widths a piece is
# 28 ms beside a decode chunk of 41 ms (8 steps): a live slot's token
# gap is bounded near 2 x the undisturbed one, where 1024 and 2048
# left the 90th percentile swinging with the arrivals (PERF.md, PR 33).
KDA_PREFILL_CHUNK = 512


def _kda_mla_moe() -> ModelOps:
    """``mla_moe``'s programs over two caches (the layers are of two
    kinds, ``mla_moe.KdaMlaMoeConfig``): the insert carries a row's
    state into its slot, and everything that takes a prefix to BE its
    blocks is refused: a prefix of this model is blocks and a state.
    A long prompt is prefilled in PIECES by default: a piece continues
    the scratch row's state and tails (a KDA layer re-reads nothing of
    what went before), the group prefill runs its rows one at a time
    anyway, and an unchunked prefill of thousands of tokens stalls
    every live slot (PERF.md, PR 33: ``tpot_p90_ms`` swung by 20%
    with the arrival order). With pieces, a chunk ends with its first
    row to finish (``paged_chunk_n``): a short answer's last tokens do
    not wait out the chunk's junk steps and the piece behind it."""
    from skypilot_tpu.models import kda, mla_moe
    state = ('a sequence is its latent blocks AND a recurrent state a '
             'KDA layer; no state is kept at block boundaries, so a '
             'prefix cannot be rebuilt from blocks')
    base = _mla_moe()
    return dataclasses.replace(
        base, name='kda_mla_moe', insert_paged=mla_moe.jit_insert,
        state_bytes_per_slot=lambda cfg: cfg.state_bytes_per_slot,
        step_paths=lambda pool: {'kda_step': kda.step_path(
            pool.state.shape, pool.state.dtype)},
        prefill_chunk=lambda cfg: KDA_PREFILL_CHUNK,
        paged_chunk_n=mla_moe.jit_paged_chunk_n,
        # the chunked long prefill seeds its scratch row from shared
        # blocks only where there is a trie: not here
        refuses=dict({k: v for k, v in base.refuses.items()
                      if k != 'prefill_chunk'}, **{
            'prefix sharing': state,
            'kv_tiers': state,
            'KV handoff': state}))


_BUILDERS = {'LlamaConfig': _llama, 'MlaMoeConfig': _mla_moe,
             'KdaMlaMoeConfig': _kda_mla_moe}


@functools.lru_cache(maxsize=None)
def _ops(type_name: str) -> ModelOps:
    return _BUILDERS[type_name]()


def ops_for(cfg) -> ModelOps:
    """The table's row for ``cfg``'s type."""
    name = type(cfg).__name__
    if name not in _BUILDERS:
        raise TypeError(f'no serving ops for a config of type {name!r}; '
                        f'known: {sorted(_BUILDERS)}')
    return _ops(name)
