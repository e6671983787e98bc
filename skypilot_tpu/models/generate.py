"""KV-cache autoregressive generation (the serving-side compute path).

Reference analog: the reference serves LLMs by launching JetStream / vLLM
workloads (``examples/tpu/v6e/README.md:112-118``); this is the TPU-native
in-framework equivalent: prefill + cached decode, everything jitted with
static shapes (XLA-friendly: the cache is a fixed ``max_len`` ring buffer
indexed with ``dynamic_update_slice``; the decode loop is ``lax.scan``).

Layers run under ``lax.scan`` with the per-layer cache slices as scan
xs/ys, so one compiled layer body serves any depth — same trick as the
training stack (``models/llama.py``).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from skypilot_tpu.models import llama, model_ops, moe
from skypilot_tpu.models.quantization import mm as _mm
# Compile ledger (observability/profiler.py): module-level jits
# register by name so the compile-once-per-shape promise in the
# docstring is machine-observable (skylint jit-program rule).
from skypilot_tpu.observability.profiler import profiled_jit

Params = llama.Params
_NEG_INF = -1e30


def kernel_shard_ctx(mesh, rules):
    """Hashable context that lets the paged pool's write, read and
    decode kernel run under a TP mesh (``paged._cache_step``):
    ``shard_map`` launches them per kv-head SHARD — no cross-head
    communication, so head-sharded inputs need no collectives and the
    output stays head-sharded for the wo matmul (GSPMD inserts that
    psum as usual). Without this, a ``pallas_call`` traced under GSPMD
    would all-gather the pool."""
    if mesh is None:
        return None
    return (mesh,
            rules.mesh_axes(('batch', 'heads', None)),           # q
            rules.mesh_axes(('batch', 'kv_heads', None, None)))  # k/v


@dataclasses.dataclass
class KVCache:
    """Per-layer key/value ring buffers: [L, B, Hkv, max_len, D].
    ``lengths`` is PER-ROW ([B] int32): rows advance independently, which
    is what lets the serving replica batch prompts of different lengths
    (right-padded) into one prefill/decode.

    INT8 mode (``k_s``/``v_s`` set — [L, B, Hkv, max_len] fp32 scales):
    k/v hold int8 codes with a symmetric per-(layer, row, head, position)
    scale over the D dim. Decode is bound by streaming the cache from
    HBM, so halving KV bytes is the same lever as int8 weights; both
    scales fold into the attention matmuls per POSITION (keys: post-QK
    logits product; values: into the probs before PV), never
    rematerializing a full-precision cache.

    A latent (MLA) cache is ONE plane: ``k`` [L, B, 1, max_len, W] and
    ``v`` None (models/mla_moe.py)."""
    k: jax.Array
    v: Optional[jax.Array]
    lengths: jax.Array  # [B] int32: tokens currently cached per row
    k_s: Optional[jax.Array] = None
    v_s: Optional[jax.Array] = None

    @property
    def quantized(self) -> bool:
        return self.k_s is not None


jax.tree_util.register_dataclass(
    KVCache, data_fields=['k', 'v', 'lengths', 'k_s', 'v_s'],
    meta_fields=[])


def init_cache(cfg: llama.LlamaConfig, batch: int, max_len: int,
               dtype=None, kv_sharding=None,
               lengths_sharding=None, quantize: bool = False,
               kv_scale_sharding=None) -> KVCache:
    """Optional shardings allocate the buffers BORN sharded (a cache
    sized to fit only spread over a slice must never transit one chip);
    None = default placement. This is the one definition of the cache
    layout — sharded and single-device paths must not diverge.
    ``quantize=True`` = int8 codes + fp32 per-position scales."""
    dtype = dtype or cfg.dtype
    shape = (cfg.n_layers, batch, cfg.n_kv_heads, max_len, cfg.head_dim)
    if quantize:
        s_shape = shape[:-1]
        return KVCache(
            k=jnp.zeros(shape, jnp.int8, device=kv_sharding),
            v=jnp.zeros(shape, jnp.int8, device=kv_sharding),
            lengths=jnp.zeros((batch,), jnp.int32,
                              device=lengths_sharding),
            k_s=jnp.zeros(s_shape, jnp.float32, device=kv_scale_sharding),
            v_s=jnp.zeros(s_shape, jnp.float32, device=kv_scale_sharding))
    return KVCache(k=jnp.zeros(shape, dtype, device=kv_sharding),
                   v=jnp.zeros(shape, dtype, device=kv_sharding),
                   lengths=jnp.zeros((batch,), jnp.int32,
                                     device=lengths_sharding))


def _cached_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                      positions: jax.Array, valid_len: jax.Array,
                      k_s: Optional[jax.Array] = None,
                      v_s: Optional[jax.Array] = None) -> jax.Array:
    """q: [B, S, Hq, D] (absolute ``positions`` [B, S]);
    k/v_cache: [B, Hkv, max_len, D] already containing this block's keys.
    Attends causally over the first ``valid_len[b]`` cache slots per row
    (padded cache slots beyond a row's valid length are never attended).
    With int8 caches, ``k_s``/``v_s`` [B, Hkv, max_len] fold in per
    position: keys scale the post-QK logits, values scale the probs
    before PV — the full-precision cache never materializes."""
    b, s, hq, d = q.shape
    hkv = k_cache.shape[1]
    group = hq // hkv
    max_len = k_cache.shape[2]
    qg = q.transpose(0, 2, 1, 3).reshape(b, hkv, group, s, d)
    scale = d ** -0.5
    logits = jnp.einsum('bhgqd,bhkd->bhgqk', qg,
                        k_cache.astype(q.dtype),
                        preferred_element_type=jnp.float32) * scale
    if k_s is not None:
        logits = logits * k_s[:, :, None, None, :]
    ki = jax.lax.broadcasted_iota(jnp.int32, (b, 1, 1, s, max_len), 4)
    qi = positions[:, None, None, :, None]  # absolute query positions
    if valid_len.ndim == 0:  # uniform batch: scalar broadcast
        mask = (ki <= qi) & (ki < valid_len)
    else:
        mask = (ki <= qi) & (ki < valid_len[:, None, None, None, None])
    logits = jnp.where(mask, logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    if v_s is not None:
        probs = probs * v_s[:, :, None, None, :]
    out = jnp.einsum('bhgqk,bhkd->bhgqd', probs.astype(q.dtype),
                     v_cache.astype(q.dtype),
                     preferred_element_type=jnp.float32)
    return out.reshape(b, hkv * group, s, d).transpose(0, 2, 1, 3).astype(
        q.dtype)


def _row_update(cache: jax.Array, new: jax.Array,
                starts: jax.Array) -> jax.Array:
    """Write ``new`` [B, Hkv, S, D] into ``cache`` [B, Hkv, max_len, D] at
    per-row offsets ``starts`` [B] (vmapped dynamic_update_slice — rows
    advance independently under batched decode)."""
    def one(c, n, s):
        return jax.lax.dynamic_update_slice(c, n, (0, s, 0))
    return jax.vmap(one)(cache, new, starts)


def _row_update_scale(cache: jax.Array, new: jax.Array,
                      starts: jax.Array) -> jax.Array:
    """[B, Hkv, max_len] scale-cache counterpart of ``_row_update``."""
    def one(c, n, s):
        return jax.lax.dynamic_update_slice(c, n, (0, s))
    return jax.vmap(one)(cache, new, starts)


def _quantize_block(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """[B, Hkv, S, D] -> (int8 codes, [B, Hkv, S] fp32 scales):
    symmetric per-position max|x|/127 over D (same recipe as weight
    quantization, models/quantization.py)."""
    x32 = x.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(x32), axis=-1) / 127.0, 1e-8)
    q8 = jnp.clip(jnp.round(x32 / s[..., None]), -127,
                  127).astype(jnp.int8)
    return q8, s


def _write_block(cache_arr: jax.Array, scale_arr: Optional[jax.Array],
                 block: jax.Array, starts: jax.Array):
    """Write a [B, Hkv, S, D] block at scalar/per-row offsets,
    quantizing on the way in when the cache is int8 (scale_arr set).
    Uniform batches (scalar ``starts``) take single dynamic_update_slices
    — measurably faster than the per-row vmap, which is reserved for
    genuinely mixed-length serving batches."""
    if scale_arr is not None:
        block, s = _quantize_block(block)
    else:
        block = block.astype(cache_arr.dtype)
    if starts.ndim == 0:
        cache_arr = jax.lax.dynamic_update_slice(cache_arr, block,
                                                 (0, 0, starts, 0))
        if scale_arr is not None:
            scale_arr = jax.lax.dynamic_update_slice(scale_arr, s,
                                                     (0, 0, starts))
    else:
        cache_arr = _row_update(cache_arr, block, starts)
        if scale_arr is not None:
            scale_arr = _row_update_scale(scale_arr, s, starts)
    return cache_arr, scale_arr


def _qkv_proj(cfg: llama.LlamaConfig, x: jax.Array, layer: Params,
              positions: jax.Array):
    """Shared attention front half (norm + QKV projections + RoPE) —
    one definition for the dense-cache and paged layers; only
    the cache write/read strategy differs between them."""
    h = llama.rms_norm(x, layer['attn_norm'], cfg.norm_eps)
    # _mm = einsum that transparently handles int8 weight-only
    # quantized leaves (models/quantization.py) — the serving
    # deployment path; full-precision weights take the same route.
    q = _mm(h, layer['wq'], 'bsd,dhk->bshk')
    k = _mm(h, layer['wk'], 'bsd,dhk->bshk')
    v = _mm(h, layer['wv'], 'bsd,dhk->bshk')
    q = llama.rope(q, positions, cfg.rope_theta)
    k = llama.rope(k, positions, cfg.rope_theta)
    return q, k, v


def _mlp_tail(cfg: llama.LlamaConfig, x: jax.Array, layer: Params,
              token_mask: Optional[jax.Array]):
    """Shared decoder-block back half (post-attention norm + MoE or
    dense MLP), residual included. ``token_mask`` [B, S] (MoE only)
    keeps padded/junk positions out of expert routing."""
    h = llama.rms_norm(x, layer['mlp_norm'], cfg.norm_eps)
    if cfg.num_experts > 0:
        mlp_out, _ = moe.moe_mlp(h, layer['moe'], cfg.num_experts,
                                 cfg.expert_top_k,
                                 cfg.expert_capacity_factor,
                                 token_mask=token_mask)
        return x + mlp_out
    gate = _mm(h, layer['w_gate'], 'bsd,df->bsf')
    up = _mm(h, layer['w_up'], 'bsd,df->bsf')
    return x + _mm(jax.nn.silu(gate) * up, layer['w_down'],
                   'bsf,fd->bsd')


def _cached_layer(cfg: llama.LlamaConfig, x: jax.Array, layer: Params,
                  positions: jax.Array, k_cache: jax.Array,
                  v_cache: jax.Array, cache_lens: jax.Array,
                  valid: jax.Array,
                  active_rows: Optional[jax.Array] = None,
                  k_s: Optional[jax.Array] = None,
                  v_s: Optional[jax.Array] = None):
    """One decoder block writing this block's K/V into the cache.
    x: [B, S, d]; k/v_cache: [B, Hkv, max_len, D]; ``cache_lens`` [B];
    ``valid`` [B] = cache_lens + real new tokens per row (< S for padded
    rows); ``active_rows`` [B] bool marks rows that are live requests —
    the continuous-batching engine (``models/engine.py``) decodes its
    FULL slot batch every step, and a freed slot's junk row must not
    consume MoE expert capacity (attention is per-row, so only expert
    routing couples rows); returns (x, k, v)."""
    q, k, v = _qkv_proj(cfg, x, layer, positions)
    # Write the new keys/values at [start, start + S) (quantizing on the
    # way in for int8 caches). Short rows of a padded batch write junk
    # beyond their real length; it is never attended (valid mask) and
    # each decode step overwrites the next junk slot first.
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    k_cache, k_s = _write_block(k_cache, k_s, kt, cache_lens)
    v_cache, v_s = _write_block(v_cache, v_s, vt, cache_lens)
    att = _cached_attention(q, k_cache, v_cache, positions, valid,
                            k_s, v_s)
    x = x + _mm(att, layer['wo'], 'bshk,hkd->bsd')
    # MoE decode: same GShard dense-einsum dispatch as training
    # (models/moe.py) — at S=1 the "token" dim is just the batch, and
    # the static capacity keeps decode shapes compile-once. The aux
    # loss is irrelevant at inference. Padded positions of a
    # mixed-length batch are masked OUT of routing so their junk
    # tokens never consume expert capacity (they could otherwise
    # displace other rows' real tokens under the choice-major
    # capacity cumsum).
    if valid.ndim == 0 and active_rows is None:
        token_mask = None  # uniform batch: every position is real
    else:
        vb = valid if valid.ndim == 0 else valid[:, None]
        mask = positions < vb
        if active_rows is not None:
            mask = mask & active_rows[:, None]
        token_mask = mask.astype(x.dtype)
    x = _mlp_tail(cfg, x, layer, token_mask)
    return x, k_cache, v_cache, k_s, v_s


def forward_cached(params: Params, tokens: jax.Array,
                   cache: KVCache, cfg: llama.LlamaConfig,
                   row_lens: Optional[jax.Array] = None,
                   active_rows: Optional[jax.Array] = None,
                   all_logits: bool = False
                   ) -> Tuple[jax.Array, KVCache]:
    """Run ``tokens`` [B, S] through the model appending to ``cache``;
    returns (logits for each row's LAST REAL position [B, vocab], updated
    cache). Works for prefill (S = padded prompt length) and decode
    (S = 1), dense and MoE models alike. ``row_lens`` [B] gives each row's
    real token count within ``tokens`` (defaults to S — unpadded batch);
    rows advance independently, enabling mixed-length serving batches.
    ``active_rows`` [B] bool (optional) marks live rows; see
    ``_cached_layer`` — only MoE expert routing couples rows."""
    b, s = tokens.shape
    uniform = row_lens is None  # STATIC: picks the cheap scalar-offset path
    if uniform:
        # All rows share lengths[0] (generate() without prompt_lengths
        # maintains this invariant for the cache's whole lifetime).
        start = cache.lengths[0]
        positions = (start + jnp.broadcast_to(
            jnp.arange(s, dtype=jnp.int32), (b, s)))
        valid = start + s           # scalar
        new_lengths = cache.lengths + s
        write_start = start         # scalar -> single dynamic_update_slice
    else:
        positions = (cache.lengths[:, None] + jnp.broadcast_to(
            jnp.arange(s, dtype=jnp.int32), (b, s)))
        valid = cache.lengths + row_lens  # [B]
        new_lengths = valid
        write_start = cache.lengths       # [B] -> per-row writes
    x = params['embed'].astype(cfg.dtype)[tokens]

    quantized = cache.quantized  # STATIC: pytree structure per jit key

    def body(carry, xs):
        x = carry
        if quantized:
            layer, k_c, v_c, ks_c, vs_c = xs
        else:
            layer, k_c, v_c = xs
            ks_c = vs_c = None
        x, k_c, v_c, ks_c, vs_c = _cached_layer(
            cfg, x, layer, positions, k_c, v_c, write_start, valid,
            active_rows, ks_c, vs_c)
        ys = (k_c, v_c, ks_c, vs_c) if quantized else (k_c, v_c)
        return x, ys

    if quantized:
        xs = (params['layers'], cache.k, cache.v, cache.k_s, cache.v_s)
        x, (new_k, new_v, new_ks, new_vs) = jax.lax.scan(body, x, xs)
    else:
        xs = (params['layers'], cache.k, cache.v)
        x, (new_k, new_v) = jax.lax.scan(body, x, xs)
        new_ks = new_vs = None
    x = llama.rms_norm(x, params['final_norm'], cfg.norm_eps)
    if uniform:
        last = x[:, -1]
    else:
        # Each row's logits come from its own last real token
        # (row_lens - 1), not the padded tail.
        last = jnp.take_along_axis(
            x, (row_lens - 1)[:, None, None].astype(jnp.int32), axis=1
        )[:, 0]
    new_cache = KVCache(k=new_k, v=new_v, lengths=new_lengths,
                        k_s=new_ks, v_s=new_vs)
    if all_logits:
        # Per-POSITION logits [B, S, V]: speculative verification needs
        # the target's prediction after every proposed token, not just
        # the block's last (S is the small draft window, so the extra
        # lm_head matmul is k rows, not a memory hazard).
        return (_mm(x, params['lm_head'], 'bsd,dv->bsv',
                    preferred_element_type=jnp.float32), new_cache)
    logits = _mm(last, params['lm_head'], 'bd,dv->bv',
                 preferred_element_type=jnp.float32)
    return logits, new_cache


def _sample(logits: jax.Array, temperature: float,
            key: Optional[jax.Array], top_k: int = 0,
            top_p: float = 1.0) -> jax.Array:
    """Scalar-config sampling for the batch path (models/sampling.py has
    the per-row vector core shared with the continuous engine)."""
    if temperature == 0.0 or key is None:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    from skypilot_tpu.models import sampling
    b = logits.shape[0]
    filters_on = top_k > 0 or top_p < 1.0  # off: skip the vocab sort
    return sampling.sample(
        logits, jnp.full((b,), temperature, jnp.float32), key,
        jnp.full((b,), top_k, jnp.int32) if filters_on else None,
        jnp.full((b,), top_p, jnp.float32) if filters_on else None)


# Module-level jits: the caches are keyed by (shapes, static args) and
# persist across generate() calls — a serving replica compiles once per
# (batch, prompt_len, max_len, n, temperature) shape, then decodes at
# steady-state speed.
_jit_prefill = profiled_jit('generate.prefill', forward_cached,
                            static_argnums=(3,))


def truncate_at_stop(tokens, eos):
    """Cut a generated row at its first stop id, INCLUSIVE. The single
    definition of stop semantics — the continuous engine and the
    window-batched path must never diverge. Returns (tokens, hit)."""
    if eos:
        for j, t in enumerate(tokens):
            if t in eos:
                return tokens[:j + 1], True
    return tokens, False


def pad_prompts(rows, pad_id: int = 0) -> Tuple[jax.Array, jax.Array]:
    """Right-pad a list of variable-length token rows into
    (tokens [B, S_max], lengths [B]) for a mixed-length serving batch."""
    import numpy as np
    lens = [len(r) for r in rows]
    s = max(lens)
    out = np.full((len(rows), s), pad_id, np.int32)
    for i, r in enumerate(rows):
        out[i, :len(r)] = np.asarray(r, np.int32)
    return jnp.asarray(out), jnp.asarray(lens, jnp.int32)


def _decode_scan_impl(params, cache, first, key, cfg, n, temps,
                      top_ks, top_ps, uniform):
    """``temps`` [B] / ``top_ks`` [B] / ``top_ps`` [B] ride as DATA
    (``top_ks``/``top_ps`` may be None = filters off, skipping the
    vocab sort): client-supplied sampling params must not key the jit
    cache, or every distinct (temperature, top_k, top_p) combination
    costs a full XLA recompile — top_p alone has unbounded distinct
    float values (r4 advisor low). Only the None/array pytree structure
    gives a second cached variant (same scheme as the engine's
    ``_paged_chunk_impl``)."""
    from skypilot_tpu.models import sampling
    forward = model_ops.ops_for(cfg).forward_cached

    def step(carry, _):
        cache, token, key = carry
        row_lens = (None if uniform
                    else jnp.ones((token.shape[0],), jnp.int32))
        logits, cache = forward(params, token[:, None], cache, cfg,
                                row_lens)
        key, sub = jax.random.split(key)
        nxt = sampling.sample(logits, temps, sub, top_ks, top_ps)
        return (cache, nxt, key), nxt

    (_, _, _), toks = jax.lax.scan(step, (cache, first, key),
                                   None, length=n - 1)
    return toks


_jit_decode_scan = profiled_jit('generate.decode_scan',
                                _decode_scan_impl,
                                static_argnums=(4, 5, 9))


def generate(params: Params, cfg: llama.LlamaConfig,
             prompt: jax.Array, max_new_tokens: int,
             temperature: float = 0.0,
             key: Optional[jax.Array] = None,
             max_len: Optional[int] = None,
             prompt_lengths: Optional[jax.Array] = None,
             kv_quantize: bool = False, top_k: int = 0,
             top_p: float = 1.0) -> jax.Array:
    """prompt: [B, S_p] int32 -> [B, max_new_tokens] generated ids.
    Greedy when temperature == 0 (deterministic parity with full forward);
    one jitted prefill + one jitted lax.scan of decode steps.
    ``prompt_lengths`` [B] marks each row's real prompt length when the
    batch is right-padded (``pad_prompts``) — rows generate from their own
    last real token. ``kv_quantize`` = int8 KV cache (halves the decode
    step's dominant HBM stream; see ``KVCache``). ``top_k``/``top_p``
    filter sampled rows (models/sampling.py); ignored when greedy."""
    b, s_p = prompt.shape
    max_len = max_len or min(cfg.max_seq_len, s_p + max_new_tokens)
    assert s_p + max_new_tokens <= max_len, (s_p, max_new_tokens, max_len)
    if top_k < 0 or not 0.0 < top_p <= 1.0:
        # top_p <= 0 would mask every token (uniform-random garbage).
        raise ValueError('top_k must be >= 0 and top_p in (0, 1]')
    ops = model_ops.ops_for(cfg)
    cache = ops.init_cache(cfg, b, max_len, quantize=kv_quantize)
    if temperature > 0.0 and key is None:
        raise ValueError('temperature > 0 requires a PRNG key')
    if key is None:
        key = jax.random.PRNGKey(0)  # unused in the greedy branch

    logits, cache = ops.prefill(params, prompt, cache, cfg,
                                prompt_lengths)
    if temperature > 0.0:
        key, first_key = jax.random.split(key)
    else:
        first_key = None
    first = _sample(logits, temperature, first_key, top_k, top_p)

    if max_new_tokens == 1:
        return first[:, None]
    filters_on = top_k > 0 or top_p < 1.0
    rest = _jit_decode_scan(
        params, cache, first, key, cfg, max_new_tokens,
        jnp.full((b,), temperature, jnp.float32),
        jnp.full((b,), top_k, jnp.int32) if filters_on else None,
        jnp.full((b,), top_p, jnp.float32) if filters_on else None,
        prompt_lengths is None)  # [T-1, B]
    return jnp.concatenate([first[:, None], rest.transpose(1, 0)], axis=1)
