"""Pallas flash-decode: single-pass cached attention for one new token.

Reference analog: the reference's serving engines carry fused decode
attention kernels (JetStream's pallas kernels, vLLM's paged attention);
the hot op here is the decode step's attention over the WHOLE KV cache
— [B, Hq, D] queries against [B, Hkv, M, D] keys/values every token.

The XLA path (``generate._cached_attention``) materializes the
[B, Hkv, G, 1, M] fp32 logits (plus the softmax intermediates) in HBM
between its two einsums; at long context that tensor rivals the KV read
itself. This kernel streams the cache once through VMEM with an online
softmax (same recipe as the training kernel, ``ops/attention.py``) — no
logits tensor ever exists in HBM, so decode stays at the KV-stream
bandwidth floor.

Layout: grid (B, Hkv); each program owns one row's one kv head — its
query GROUP [G, D] and the head's [M, D] cache slice. Per-row valid
lengths arrive via scalar prefetch and mask tail positions in-kernel.
int8 caches fold their per-position scales exactly like the jnp path:
key scales into the post-QK logits, value scales into the probs.

The int8 scales travel as lane-dense ``[1, M]`` rows: a ``[M, 1]``
column pads to 128 lanes per position in VMEM, which at the cap would
outweigh the cache slices themselves.

OPT-IN (``SKYTPU_DECODE_KERNEL=pallas``): accumulation order differs
from the XLA path, so outputs match to tolerance, not bit-exactly — and
the serving engine's exact-parity contract keeps the XLA path as its
default.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_K = 512
_NEG_INF = -1e30
# Both K and V slices ([M, D] each, plus scales in int8 mode) sit whole
# in VMEM per program; cap M*D so they fit the scoped-VMEM limit.
# M = 16384 at D = 128, bf16 and int8, is what the chip_smoke `kernels`
# phase compiles on a v5e. Beyond the cap callers take the XLA path —
# same policy as the training kernel's _VMEM_CAP_ELEMS.
VMEM_CAP_ELEMS = 2 * 1024 * 1024


def fits(max_len: int, head_dim: int) -> bool:
    """True when the kernel can handle this cache geometry: the [M, D]
    slices fit the VMEM budget and M is 128-divisible so a divisor
    block size exists (pl.ds CLAMPS out-of-range starts — a partial
    tail block would silently mislabel key positions)."""
    return max_len % 128 == 0 and max_len * head_dim <= VMEM_CAP_ELEMS


def _pick_block(m: int) -> int:
    """Largest divisor of m that is <= BLOCK_K (m is 128-divisible per
    ``fits``, so the result is always >= 128)."""
    b = min(BLOCK_K, m)
    while m % b:
        b -= 128
    return b


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, *rest, block_k: int,
                   max_len: int, quant: bool):
    """q_ref [G, D]; k_ref/v_ref [M, D] (one (row, kv-head) slice);
    len_ref: scalar-prefetched [B] valid lengths. ``quant`` (static):
    k/v are int8 codes and ``rest`` leads with their [1, M] fp32
    per-position scales, folded exactly where the jnp path folds them
    (keys into the logits, values into the probs). ONE body serves both
    modes so the masking/accumulation can never diverge."""
    if quant:
        ks_ref, vs_ref, o_ref = rest
    else:
        (o_ref,) = rest
    b = pl.program_id(0)
    q = q_ref[...]
    g, d = q.shape
    scale = d ** -0.5
    valid = len_ref[b]
    num_blocks = pl.cdiv(max_len, block_k)

    def body(kb, carry):
        acc, m_prev, l_prev = carry
        start = pl.multiple_of(kb * block_k, block_k)
        kblk = k_ref[pl.ds(start, block_k), :]
        s = jax.lax.dot_general(
            q, kblk.astype(q.dtype), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [G, bk]
        if quant:
            s = s * ks_ref[:, pl.ds(start, block_k)]
        ki = start + jax.lax.broadcasted_iota(jnp.int32, (g, block_k), 1)
        s = jnp.where(ki < valid, s, _NEG_INF)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        vblk = v_ref[pl.ds(start, block_k), :]
        if quant:
            p = p * vs_ref[:, pl.ds(start, block_k)]
        acc = acc * alpha + jax.lax.dot_general(
            p.astype(q.dtype), vblk.astype(q.dtype),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return acc, m_new, l_new

    acc0 = jnp.zeros((g, d), jnp.float32)
    m0 = jnp.full((g, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((g, 1), jnp.float32)
    acc, _, l = jax.lax.fori_loop(0, num_blocks, body, (acc0, m0, l0))
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def flash_decode(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                 lengths: jax.Array,
                 k_s: Optional[jax.Array] = None,
                 v_s: Optional[jax.Array] = None,
                 interpret: bool = False,
                 block_k: Optional[int] = None) -> jax.Array:
    """q [B, Hq, D] (the single decode position), k/v_cache
    [B, Hkv, M, D], lengths [B] int32 (attend positions < lengths[b]),
    optional int8-cache scales [B, Hkv, M] -> out [B, Hq, D]."""
    b, hq, d = q.shape
    hkv, m = k_cache.shape[1], k_cache.shape[2]
    group = hq // hkv
    if block_k is None:
        if m % 128 == 0:
            block_k = _pick_block(m)
        else:
            # Callers should gate on fits(); small/odd caches (tests,
            # tiny models) fall back to one exact full-M block.
            block_k = m
    qg = q.reshape(b, hkv, group, d)
    grid = (b, hkv)
    common = dict(block_k=block_k, max_len=m)
    qspec = pl.BlockSpec((None, None, group, d),
                         lambda bi, hi, *_: (bi, hi, 0, 0))
    kvspec = pl.BlockSpec((None, None, m, d),
                          lambda bi, hi, *_: (bi, hi, 0, 0))
    out_spec = pl.BlockSpec((None, None, group, d),
                            lambda bi, hi, *_: (bi, hi, 0, 0))
    out_shape = jax.ShapeDtypeStruct((b, hkv, group, d), q.dtype)
    if k_s is None:
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid,
            in_specs=[qspec, kvspec, kvspec], out_specs=out_spec)
        out = pl.pallas_call(
            functools.partial(_decode_kernel, quant=False, **common),
            grid_spec=grid_spec, out_shape=out_shape,
            interpret=interpret,
        )(lengths, qg, k_cache, v_cache)
    else:
        sspec = pl.BlockSpec((None, None, 1, m),
                             lambda bi, hi, *_: (bi, hi, 0, 0))
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid,
            in_specs=[qspec, kvspec, kvspec, sspec, sspec],
            out_specs=out_spec)
        out = pl.pallas_call(
            functools.partial(_decode_kernel, quant=True, **common),
            grid_spec=grid_spec, out_shape=out_shape,
            interpret=interpret,
        )(lengths, qg, k_cache, v_cache, k_s[:, :, None, :],
          v_s[:, :, None, :])
    return out.reshape(b, hq, d)
