"""Flash attention for TPU (pallas) with a reference jnp fallback.

Design (pallas_guide.md patterns):
  * forward: grid = (batch, q_heads, S // block_q); each program owns one
    query block and streams K/V for its (batch, kv_head) through VMEM.
  * online softmax: running max ``m``, normalizer ``l``, fp32 accumulator —
    no S x S matrix ever materializes in HBM. The log-sum-exp per query row
    is written out as a residual for the backward pass.
  * matmuls run in the input dtype (bf16 on TPU) with fp32 accumulation
    (``preferred_element_type``) — MXU-native mixed precision; softmax math
    is fp32 on the VPU. Block sizes are large (256-1024) so each MXU issue
    amortizes the serialized softmax chain.
  * causal masking prunes the KV loop to blocks at-or-before the query block
    (the loop bound is computed from ``program_id``, so the compiler still
    sees a static grid).
  * GQA: q_heads grouped onto n_kv_heads; the kv head index is derived from
    the q head index.

Backward pass (fused pallas kernels, FlashAttention-2 style):
  * residuals = (q, k, v, o, lse); ``delta = rowsum(do * o)`` is computed by
    XLA outside the kernels (it fuses into the surrounding elementwise ops).
  * per-row stats (lse, delta) carry a trailing singleton dim ([B, Hq, S, 1])
    — Mosaic requires the minor dim be 128-divisible or the full array dim.
  * dQ kernel: same grid shape as forward; recomputes p = exp(s - lse) block
    by block, accumulates dq += scale * ds @ K in fp32.
  * dK/dV kernel: grid = (batch, kv_heads, S // block, S // block) with the
    query-block sweep innermost; dk/dv output blocks stay VMEM-resident in
    fp32 and accumulate across the sweep. Causally-skipped iterations do no
    compute, and their index maps repeat the previous block so no DMA is
    issued either.
  * VMEM gate: the forward and dq kernels keep one head's full K/V
    resident; past ``_VMEM_CAP_ELEMS`` the whole op takes the reference
    (long-context training routes through parallel/ring_attention.py).

Under a multi-device mesh the kernel runs per shard (``shard_ctx`` +
``jax.shard_map`` over the batch and heads axes): the grid is per
(batch, head) with no communication, and GSPMD cannot partition a Mosaic
custom call — left bare it would gather q/k/v whole onto every chip.

The reference is taken only on a condition visible at trace time —
off-TPU without ``interpret``, or a shape the kernel cannot handle —
and the second case is logged once per shape (``FALLBACK_TAG``).

Reference counterpart: the reference delegates attention kernels to its
launched workloads (SURVEY.md §2.11); this is the TPU-native flagship-model
hot op.
"""
from __future__ import annotations

import functools
import logging
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# Tuned on v5e (see tests/test_ops_attention.py for the numerics gate).
FWD_BLOCK_Q = 256
FWD_BLOCK_K = 512
DQ_BLOCK_Q = 256
DQ_BLOCK_K = 512
DKV_BLOCK = 512
_MIN_BLOCK = 128
_NEG_INF = -1e30
# The forward and dq kernels keep one head's full K and V ([S, D] each,
# double-buffered by the pipeline) resident in VMEM; cap S*D so they fit
# the default scoped-VMEM limit. S = 8192 at D = 128 (8 MB of bf16 K/V
# buffers) is the largest the chip_smoke `kernels` phase compiles on a
# v5e; beyond it, training routes through ring attention
# (parallel/ring_attention.py) anyway.
_VMEM_CAP_ELEMS = 1024 * 1024

FALLBACK_TAG = '[kernel-fallback]'
_log = logging.getLogger(__name__)
_logged_fallbacks: set = set()


def _use_pallas() -> bool:
    return jax.default_backend() == 'tpu'


def log_fallback_once(kernel: str, shape, why: str) -> None:
    """One warning per (kernel, shape, reason): a Pallas kernel gave way
    to its jnp reference. Runs at trace time, so a jitted program logs
    when it is built, not per call. chip_smoke.py fails on this tag."""
    key = (kernel, tuple(shape), why)
    if key in _logged_fallbacks:
        return
    _logged_fallbacks.add(key)
    _log.warning('%s %s takes the jnp reference for shape %s: %s',
                 FALLBACK_TAG, kernel, tuple(shape), why)


# ---------------------------------------------------------------------------
# Reference implementation (fallback + numerics oracle)
# ---------------------------------------------------------------------------


def attention_reference(q: jax.Array, k: jax.Array, v: jax.Array,
                        causal: bool = True) -> jax.Array:
    """Plain attention. q: [B, Hq, S, D]; k/v: [B, Hkv, S, D]; fp32 softmax."""
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    assert hq % hkv == 0, (hq, hkv)
    group = hq // hkv
    qg = q.reshape(b, hkv, group, s, d)
    scale = d ** -0.5
    logits = jnp.einsum('bhgqd,bhkd->bhgqk', qg, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        qi = jax.lax.broadcasted_iota(jnp.int32, (s, s), 0)
        ki = jax.lax.broadcasted_iota(jnp.int32, (s, s), 1)
        logits = jnp.where(ki <= qi, logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum('bhgqk,bhkd->bhgqd', probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, hq, s, d).astype(q.dtype)


def _pick(block: int, s: int) -> int:
    """Largest divisor block size <= requested that divides s."""
    b = min(block, s)
    while s % b:
        b //= 2
    return max(b, _MIN_BLOCK)


# ---------------------------------------------------------------------------
# Pallas forward kernel
# ---------------------------------------------------------------------------


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, causal: bool,
                      block_q: int, block_k: int, seq_len: int):
    # q_ref: [block_q, D]; k_ref/v_ref: [S, D]; o_ref: [block_q, D];
    # lse_ref: [block_q, 1] fp32.
    q_blk_idx = pl.program_id(2)
    q = q_ref[...]
    d = q.shape[-1]
    scale = d ** -0.5

    q_start = q_blk_idx * block_q
    if causal:
        # Only KV blocks whose start is <= last query index participate;
        # of those, only blocks overlapping the diagonal need masking.
        num_k_blocks = (q_start + block_q + block_k - 1) // block_k
        num_inner_blocks = q_start // block_k  # fully-unmasked prefix
    else:
        num_k_blocks = pl.cdiv(seq_len, block_k)
        num_inner_blocks = num_k_blocks

    def make_body(masked):
        def body(kb, carry):
            acc, m_prev, l_prev = carry
            k_start = kb * block_k
            kblk = k_ref[pl.ds(k_start, block_k), :]
            vblk = v_ref[pl.ds(k_start, block_k), :]
            s_ij = jax.lax.dot_general(
                q, kblk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if masked:
                qi = q_start + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 0)
                ki = k_start + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 1)
                s_ij = jnp.where(ki <= qi, s_ij, _NEG_INF)
            m_cur = jnp.max(s_ij, axis=-1, keepdims=True)  # [block_q, 1]
            m_new = jnp.maximum(m_prev, m_cur)
            p = jnp.exp(s_ij - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc = acc * alpha + jax.lax.dot_general(
                p.astype(vblk.dtype), vblk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return acc, m_new, l_new
        return body

    acc0 = jnp.zeros((block_q, d), jnp.float32)
    m0 = jnp.full((block_q, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    carry = jax.lax.fori_loop(0, num_inner_blocks, make_body(False),
                              (acc0, m0, l0))
    acc, m, l = jax.lax.fori_loop(num_inner_blocks, num_k_blocks,
                                  make_body(causal), carry)
    l = jnp.maximum(l, 1e-30)
    o_ref[...] = (acc / l).astype(o_ref.dtype)
    lse_ref[...] = m + jnp.log(l)


def _flash_fwd(q: jax.Array, k: jax.Array, v: jax.Array, causal: bool,
               interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """Returns (o [B, Hq, S, D], lse [B, Hq, S, 1] fp32)."""
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    group = hq // hkv
    block_q = _pick(FWD_BLOCK_Q, s)
    block_k = _pick(FWD_BLOCK_K, s)
    grid = (b, hq, s // block_q)
    kernel = functools.partial(_flash_fwd_kernel, causal=causal,
                               block_q=block_q, block_k=block_k, seq_len=s)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            # `None` block dims are squeezed: refs arrive as [block_q, D] /
            # [S, D] inside the kernel.
            pl.BlockSpec((None, None, block_q, d),
                         lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((None, None, s, d),
                         lambda bi, hi, qi, _g=group: (bi, hi // _g, 0, 0)),
            pl.BlockSpec((None, None, s, d),
                         lambda bi, hi, qi, _g=group: (bi, hi // _g, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, block_q, d),
                         lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((None, None, block_q, 1),
                         lambda bi, hi, qi: (bi, hi, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((b, hq, s, 1), jnp.float32),
        ],
        interpret=interpret,
        name='flash_fwd',
    )(q, k, v)


# ---------------------------------------------------------------------------
# Pallas backward kernels
# ---------------------------------------------------------------------------


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, *, causal: bool, block_q: int, block_k: int,
                         seq_len: int):
    # q/do/dq: [block_q, D]; k/v: [S, D]; lse/delta: [block_q, 1] fp32.
    q_blk_idx = pl.program_id(2)
    q = q_ref[...]
    do = do_ref[...]
    d = q.shape[-1]
    scale = d ** -0.5
    lse = lse_ref[...]
    delta = delta_ref[...]

    q_start = q_blk_idx * block_q
    if causal:
        num_k_blocks = (q_start + block_q + block_k - 1) // block_k
        num_inner_blocks = q_start // block_k  # fully-unmasked prefix
    else:
        num_k_blocks = pl.cdiv(seq_len, block_k)
        num_inner_blocks = num_k_blocks

    def make_body(masked):
        def body(kb, acc):
            k_start = kb * block_k
            kblk = k_ref[pl.ds(k_start, block_k), :]
            vblk = v_ref[pl.ds(k_start, block_k), :]
            s_ij = jax.lax.dot_general(
                q, kblk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            p = jnp.exp(s_ij - lse)  # [block_q, block_k]
            if masked:
                qi = q_start + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 0)
                ki = k_start + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 1)
                p = jnp.where(ki <= qi, p, 0.0)
            dp = jax.lax.dot_general(
                do, vblk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            ds = (p * (dp - delta)) * scale
            return acc + jax.lax.dot_general(
                ds.astype(kblk.dtype), kblk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        return body

    acc0 = jnp.zeros((block_q, d), jnp.float32)
    acc = jax.lax.fori_loop(0, num_inner_blocks, make_body(False), acc0)
    acc = jax.lax.fori_loop(num_inner_blocks, num_k_blocks, make_body(causal),
                            acc)
    dq_ref[...] = acc.astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, *, causal: bool, group: int,
                          block: int):
    # Grid: (batch, kv_head, kv_block, q_block) — q_block innermost, so
    # dk/dv output blocks stay VMEM-resident and accumulate across q blocks
    # (fp32 outputs; cast to input dtype outside the kernel).
    # q/do: [group, block, D]; k/v: [block, D];
    # lse/delta: [group, block, 1] fp32; dk/dv: [block, D] fp32.
    kb = pl.program_id(2)
    qb = pl.program_id(3)
    d = k_ref.shape[-1]
    scale = d ** -0.5
    start_qb = kb if causal else 0

    @pl.when(qb == start_qb)
    def _init():
        dk_ref[...] = jnp.zeros_like(dk_ref)
        dv_ref[...] = jnp.zeros_like(dv_ref)

    def _run(masked):
        kblk = k_ref[...]
        vblk = v_ref[...]
        k_start = kb * block
        q_start = qb * block
        dk_acc = jnp.zeros((block, d), jnp.float32)
        dv_acc = jnp.zeros((block, d), jnp.float32)
        for g in range(group):  # static unroll over the GQA group
            qblk = q_ref[g]
            doblk = do_ref[g]
            lse = lse_ref[g]      # [block, 1]
            delta = delta_ref[g]  # [block, 1]
            s_ij = jax.lax.dot_general(
                qblk, kblk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            p = jnp.exp(s_ij - lse)  # [block, block]
            if masked:
                qi = q_start + jax.lax.broadcasted_iota(
                    jnp.int32, (block, block), 0)
                ki = k_start + jax.lax.broadcasted_iota(
                    jnp.int32, (block, block), 1)
                p = jnp.where(ki <= qi, p, 0.0)
            dp = jax.lax.dot_general(
                doblk, vblk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            ds = (p * (dp - delta)) * scale
            # dv += p^T @ do ; dk += ds^T @ q  (contract over the Q rows)
            dv_acc = dv_acc + jax.lax.dot_general(
                p.astype(doblk.dtype), doblk, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dk_acc = dk_acc + jax.lax.dot_general(
                ds.astype(qblk.dtype), qblk, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        dk_ref[...] += dk_acc
        dv_ref[...] += dv_acc

    if causal:
        # Only the diagonal block needs the causal mask (BLOCK_K == BLOCK_Q
        # keeps it block-aligned); strictly-below-diagonal blocks skip the
        # iota/compare/select passes entirely.
        pl.when(qb == kb)(lambda: _run(True))
        pl.when(qb > kb)(lambda: _run(False))
    else:
        _run(False)


def _flash_bwd(q, k, v, o, lse, do, causal: bool, interpret: bool = False):
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    group = hq // hkv
    # delta = rowsum(do * o) per query row; XLA fuses this elementwise pass.
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)  # [B, Hq, S, 1]

    block_q = _pick(DQ_BLOCK_Q, s)
    block_k = _pick(DQ_BLOCK_K, s)
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, causal=causal,
                          block_q=block_q, block_k=block_k, seq_len=s),
        grid=(b, hq, s // block_q),
        in_specs=[
            pl.BlockSpec((None, None, block_q, d),
                         lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((None, None, s, d),
                         lambda bi, hi, qi, _g=group: (bi, hi // _g, 0, 0)),
            pl.BlockSpec((None, None, s, d),
                         lambda bi, hi, qi, _g=group: (bi, hi // _g, 0, 0)),
            pl.BlockSpec((None, None, block_q, d),
                         lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((None, None, block_q, 1),
                         lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((None, None, block_q, 1),
                         lambda bi, hi, qi: (bi, hi, qi, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, block_q, d),
                               lambda bi, hi, qi: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        name='flash_dq',
    )(q, k, v, do, lse, delta)

    # Reshape per-q-head tensors to [B, Hkv, group, ...] so the kv-grid
    # kernel streams its whole GQA group per query block.
    qg = q.reshape(b, hkv, group, s, d)
    dog = do.reshape(b, hkv, group, s, d)
    lseg = lse.reshape(b, hkv, group, s, 1)
    deltag = delta.reshape(b, hkv, group, s, 1)

    block = _pick(DKV_BLOCK, s)
    if causal:
        # Causally-skipped (kb, qb) iterations point at the first block that
        # will actually run, so Mosaic issues no DMA for them.
        def _qmap(bi, hi, ki, qi):
            return (bi, hi, 0, jnp.maximum(qi, ki), 0)
    else:
        def _qmap(bi, hi, ki, qi):
            return (bi, hi, 0, qi, 0)

    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, causal=causal, group=group,
                          block=block),
        grid=(b, hkv, s // block, s // block),
        in_specs=[
            pl.BlockSpec((None, None, group, block, d), _qmap),
            pl.BlockSpec((None, None, block, d),
                         lambda bi, hi, ki, qi: (bi, hi, ki, 0)),
            pl.BlockSpec((None, None, block, d),
                         lambda bi, hi, ki, qi: (bi, hi, ki, 0)),
            pl.BlockSpec((None, None, group, block, d), _qmap),
            pl.BlockSpec((None, None, group, block, 1), _qmap),
            pl.BlockSpec((None, None, group, block, 1), _qmap),
        ],
        out_specs=[
            pl.BlockSpec((None, None, block, d),
                         lambda bi, hi, ki, qi: (bi, hi, ki, 0)),
            pl.BlockSpec((None, None, block, d),
                         lambda bi, hi, ki, qi: (bi, hi, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, jnp.float32),
            jax.ShapeDtypeStruct(v.shape, jnp.float32),
        ],
        interpret=interpret,
        name='flash_dkv',
    )(qg, k, v, dog, lseg, deltag)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


# ---------------------------------------------------------------------------
# custom_vjp plumbing
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash_attention(q, k, v, causal, interpret):
    return _flash_fwd(q, k, v, causal, interpret=interpret)[0]


def _flash_attention_fwd(q, k, v, causal, interpret):
    o, lse = _flash_fwd(q, k, v, causal, interpret=interpret)
    return o, (q, k, v, o, lse)


def _flash_attention_bwd(causal, interpret, residuals, g):
    q, k, v, o, lse = residuals
    return _flash_bwd(q, k, v, o, lse, g, causal, interpret=interpret)


_flash_attention.defvjp(_flash_attention_fwd, _flash_attention_bwd)


def _unsupported(shape) -> Optional[str]:
    """Why the kernels cannot take q of this [B, Hq, S, D], or None."""
    s, d = shape[2], shape[3]
    if s % _MIN_BLOCK:
        return f'S={s} is not a multiple of {_MIN_BLOCK}'
    if d < 64:
        return f'D={d} < 64'
    if s * d > _VMEM_CAP_ELEMS:
        return (f'S*D={s * d} exceeds the resident-K/V VMEM cap '
                f'{_VMEM_CAP_ELEMS}')
    return None


def shard_ctx(mesh, rules):
    """Hashable context that runs the kernels per shard of ``mesh``:
    (mesh, q spec, k/v spec) from the logical rules — batch over the
    batch axes, heads over the heads axis. None on one device, where the
    bare call is already the whole program."""
    if mesh is None or rules is None or mesh.size == 1:
        return None
    return (mesh,
            rules.mesh_axes(('batch', 'heads', None, None)),
            rules.mesh_axes(('batch', 'kv_heads', None, None)))


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, interpret: bool = False,
                    shard=None) -> jax.Array:
    """Public entrypoint. q: [B, Hq, S, D]; k/v: [B, Hkv, S, D] (GQA ok).
    ``shard`` (``shard_ctx``) runs the kernel per mesh shard.
    ``interpret`` is for tests: the Pallas interpreter off-TPU."""
    if not (_use_pallas() or interpret):
        return attention_reference(q, k, v, causal)
    why = _unsupported(q.shape)
    if why is not None:
        log_fallback_once('flash_attention', q.shape, why)
        return attention_reference(q, k, v, causal)
    if shard is None:
        return _flash_attention(q, k, v, causal, interpret)
    # Heads shard in contiguous blocks, so each shard's q heads map onto
    # its own kv heads (the weights already require Hkv % tensor == 0).
    mesh, q_spec, kv_spec = shard
    return jax.shard_map(
        lambda q_, k_, v_: _flash_attention(q_, k_, v_, causal, interpret),
        mesh=mesh, in_specs=(q_spec, kv_spec, kv_spec), out_specs=q_spec,
        check_vma=False)(q, k, v)
