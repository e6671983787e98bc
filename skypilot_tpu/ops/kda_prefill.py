"""Pallas kernel for the chunked KDA recurrence of a prefill.

``kda_chunk`` computes what ``models/kda.chunked`` computes, for rows
of positions continuing a float32 state ``[H, dk, dv]`` a head: the
same chunks of ``kda.CHUNK`` positions, the same running sum ``G`` of
the decay inside a chunk, the same decayed pairs (exact inside blocks
of ``kda.SUB`` positions, both factors anchored at the later block's
start across blocks, so every exponent is of ``G_i - G_j`` with j <= i),
the same unit lower-triangular solve in WY form (``U = U_0 - W_k S_0``)
and the same ``S_0 -> U -> O, S_C`` over the chunks in order. Every
product is float32 at ``Precision.HIGHEST`` into float32.

What it is for: the XLA form builds its pair tensors ``[.., SUB, SUB,
dk]`` and ``[.., T, T]`` for ALL chunks at once in HBM (~2 GB accessed
a layer at one row of 512 positions and 32 heads of 128) and scans the
chunks with four small einsums a step: 1.27 ms a layer on a TPU v5e
there, this kernel 0.51 (PERF.md §6). A program takes one head of
one row and a few chunks of it; the head's state stays in a VMEM
scratch across the row's chunks (the grid's last axis walks them in
order), a chunk's pairs and inverse live in vector registers, and HBM
sees q, k, v, g and beta once, o once and the state in and out once.

Inside a chunk of T positions (nb = T / SUB blocks):

* across blocks, one matmul a later block: its rows decayed from its
  anchor against the earlier positions decayed to it;
* the exact pairs of every block at once, in SUB turns: turn ``j`` takes
  each block's ``j``-th position against the later ones of its block
  (``[T, dk]`` exponentials, the rows before ``j`` left out a sublane
  tile at a time), sums over the channels, and puts the column into
  ``A_qk``; the same column of ``Diag(beta) A_kk`` eliminates position
  ``j`` from the rows after it in its block (forward substitution, a
  column a turn), so the turns also leave each block's unit lower
  inverse ``D_a^-1``;
* the chunk's inverse block row by block row, ``X_a = D_a^-1 (I - N
  X)``, then ``U_0``, ``W_k``, and the state's turn.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_EXACT = jax.lax.Precision.HIGHEST
# Chunks a grid step, at most: a row of 512 positions is one step a
# head. A step takes ONE head: with 4 a step, their work emitted side by
# side, a layer took 12% less time on a v5e, but the kernel then cost
# every start-up 4x the lowering in every program that holds it (+16 s
# of `setup.lower_s` in the served Kimi cell, PERF.md §6).
CHUNKS_PER_STEP = 8


def _kernel(q_ref, k_ref, v_ref, g_ref, b_ref, s0_ref, s_out, o_ref,
            s_acc, *, sub: int):
    """One head of one row, ``nc`` chunks of T positions a step: q_ref,
    k_ref, g_ref [1, 1, nc, T, dk], v_ref and o_ref [1, 1, nc, T, dv],
    b_ref [1, 1, nc, T, 1]; s0_ref and s_out [1, 1, dk, dv], read at the
    row's first step and written at its last; ``s_acc`` the head's state
    in between."""
    step = pl.program_id(2)
    nc, t, dk = q_ref.shape[2:]
    nb = t // sub
    shift = sub.bit_length() - 1
    f32 = jnp.float32

    def mm(a, b, contract=((1,), (0,))):
        return jax.lax.dot_general(a, b, (contract, ((), ())),
                                   precision=_EXACT,
                                   preferred_element_type=f32)

    @pl.when(step == 0)
    def _():
        s_acc[...] = s0_ref[0, 0]

    def iota(shape, dim):
        return jax.lax.broadcasted_iota(jnp.int32, shape, dim)

    r_tt, c_tt = iota((t, t), 0), iota((t, t), 1)
    r_td = iota((t, dk), 0)
    local_tt = r_tt & (sub - 1)                  # a row's place in its block
    at_tt = c_tt - ((r_tt >> shift) << shift)    # a column's, in the row's
    eye = (r_tt == c_tt).astype(f32)

    def stack(*parts):
        """Row blocks [.., T] (an int: that many rows of zeros) -> [T, T]."""
        return jnp.concatenate(
            [jnp.zeros((p, t), f32) if isinstance(p, int) else p
             for p in parts if not isinstance(p, int) or p], axis=0)

    def blocks(a):
        return a.reshape(nb, sub, a.shape[1])

    def part(a, lo):
        """Rows ``lo:`` of each block of ``a`` [T, x] (``lo`` a whole
        number of sublane tiles)."""
        if not lo:
            return a
        return blocks(a)[:, lo:].reshape(nb * (sub - lo), a.shape[1])

    def merge(a, new, lo):
        """``a`` with rows ``lo:`` of each block from ``new``."""
        if not lo:
            return new
        return jnp.concatenate(
            [blocks(a)[:, :lo], new.reshape(nb, sub - lo, a.shape[1])],
            axis=1).reshape(a.shape)

    def rows_of(a3, j, lo):
        """Each block's ``j``-th row of ``a3`` [nb, SUB, x], repeated over
        rows ``lo:`` of its block: [nb * (SUB - lo), x]."""
        return jnp.broadcast_to(a3[:, j:j + 1], (nb, sub - lo, a3.shape[2])
                                ).reshape(nb * (sub - lo), a3.shape[2])

    # the masks a pair turn takes, from each sublane tile of a block on
    halves = tuple(range(0, sub, 8))
    at_of = {lo: part(at_tt, lo) for lo in halves}
    local_of = {lo: part(local_tt, lo) for lo in halves}

    def prepare(q, k, v, g, beta):
        """What a chunk needs of its own, before the state: (U_0, W_k,
        A_qk, q decayed, k decayed to the chunk's end, exp G_last [dk,
        1])."""
        kb = beta * k                                      # beta [T, 1]
        gc = g                          # running sum, doubling strides
        for stride in (1 << i for i in range((t - 1).bit_length())):
            gc = gc + jnp.where(r_td >= stride, pltpu.roll(gc, stride, 0),
                                0.0)
        # across blocks: both factors anchored at the later block's start
        n_out, qk_out = [None], [None]
        for a in range(1, nb):
            rows = slice(a * sub, (a + 1) * sub)
            anchor = gc[a * sub - 1:a * sub]
            left = jnp.exp(gc[rows] - anchor)
            left = jnp.concatenate([kb[rows] * left, q[rows] * left], axis=0)
            right = k * jnp.exp(jnp.where(r_td < a * sub, anchor - gc,
                                          -jnp.inf))
            outer = mm(left, right, ((1,), (1,)))           # [2 sub, T]
            n_out.append(outer[:sub])
            qk_out.append(outer[sub:])
        aqk, x = jnp.zeros((t, t), f32), eye
        g3, k3 = blocks(gc), blocks(k)
        own = {lo: (part(gc, lo), part(kb, lo), part(q, lo)) for lo in halves}
        for j in range(sub):
            # inside blocks: position j of each block against the later
            # ones of its block, every pair exactly; the rows of a block
            # before j that a sublane tile cannot leave out give exp(0)
            # here and are masked
            lo = j // 8 * 8
            g_lo, kb_lo, q_lo = own[lo]
            e = jnp.exp(jnp.minimum(g_lo - rows_of(g3, j, lo), 0.0))
            w = e * rows_of(k3, j, lo)
            col_kk = jnp.sum(kb_lo * w, axis=1, keepdims=True)
            col_qk = jnp.sum(q_lo * w, axis=1, keepdims=True)
            aqk = merge(aqk, jnp.where(at_of[lo] == j, col_qk,
                                       part(aqk, lo)), lo)
            # forward substitution, column j of each block: the rows
            # after j take off beta_i A_ij times row j (final by now)
            x = merge(x, part(x, lo) - jnp.where(local_of[lo] > j, col_kk,
                                                 0.0)
                      * rows_of(blocks(x), j, lo), lo)
        aqk = jnp.where(c_tt <= r_tt, aqk, 0.0)
        aqk = jnp.concatenate(
            [aqk[:sub]] + [aqk[a * sub:(a + 1) * sub] + qk_out[a]
                           for a in range(1, nb)], axis=0)
        # the chunk's inverse, block row by block row: X_a = D_a^-1 (I -
        # N X), X holding the rows before block a
        inv = [x[:sub]]
        for a in range(1, nb):
            y = mm(n_out[a], stack(*inv, t - a * sub))
            d = x[a * sub:(a + 1) * sub]
            inv.append(d - mm(d, stack(a * sub, y, t - (a + 1) * sub)))
        x = jnp.concatenate(inv, axis=0)
        decay = jnp.exp(gc)
        uw = mm(x, jnp.concatenate([beta * v, kb * decay], axis=1))
        dv = v.shape[1]
        last = gc[t - 1:t]
        # exp(G_last) a row of the state: lanes -> sublanes
        d_last = jnp.exp(gc[t - 8:t]).T[:, 7:8]             # [dk, 1]
        return (uw[:, :dv], uw[:, dv:], aqk, q * decay,
                k * jnp.exp(last - gc), d_last)

    def advance(c, u0, wk, aqk, qd, kd, d_last):
        """The state's turn: one pass of its weights for both of its
        products, one of u's for both of u's."""
        s = s_acc[...]
        p = mm(jnp.concatenate([wk, qd], axis=0), s)
        u = u0 - p[:t]
        r = mm(jnp.concatenate([aqk, kd.T], axis=0), u)
        o_ref[0, 0, c] = p[t:] + r[:t]
        s_acc[...] = s * d_last + r[t:]

    def chunk(c, carry):
        advance(c, *prepare(q_ref[0, 0, c], k_ref[0, 0, c], v_ref[0, 0, c],
                            g_ref[0, 0, c], b_ref[0, 0, c]))
        return carry

    jax.lax.fori_loop(0, nc, chunk, 0)

    @pl.when(step == pl.num_programs(2) - 1)
    def _():
        s_out[0, 0] = s_acc[...]


def _cut(a: jax.Array, chunk: int) -> jax.Array:
    """[B, S, H, x] -> [B, H, S / chunk, chunk, x]: ``kda.chunked``'s
    layout of a row's chunks."""
    b, s, h = a.shape[:3]
    return a.reshape(b, s // chunk, chunk, h, -1).transpose(0, 3, 1, 2, 4)


# Traced once a shape a process and lowered once a shape a program: a
# prefill program holds one call a KDA layer, and a kernel traced and
# lowered anew at each call costs every start-up seconds (PERF.md §6).
# skylint: allow-jit(an inner call of the programs that hold it, not a
# program of its own)
@functools.partial(jax.jit, static_argnames=('chunk', 'sub', 'interpret'))
def kda_chunk(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
              beta: jax.Array, state: jax.Array, *, chunk: int, sub: int,
              interpret: bool = False):
    """The recurrence over rows of positions, ``kda.chunked``'s
    arguments: q, k, g [B, S, H, dk], v [B, S, H, dv], beta [B, S, H]
    float32 (g = beta = 0 at masked positions), state [B, H, dk, dv]
    float32; ``chunk`` positions a chunk (S a multiple of it, itself a
    multiple of 8), blocks of ``sub`` (a multiple of 8 and a power of
    two that divides ``chunk``). -> (state [B, H, dk, dv], o [B, S, H,
    dv]): the state FIRST, as the prefill's map over rows carries it.
    Callers gate on ``decode_attention.kda_fits`` (``kda.chunk_path``)."""
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    if (s % chunk or chunk % sub or chunk % 8 or sub % 8
            or sub & (sub - 1)):
        raise ValueError(f'kda_chunk: {s} positions in chunks of {chunk} '
                         f'and blocks of {sub}')
    n = s // chunk
    nc = math.gcd(n, CHUNKS_PER_STEP)
    f32 = jnp.float32
    ins = [_cut(a.astype(f32), chunk) for a in (q, k, v, g, beta[..., None])]

    def at(width):
        return pl.BlockSpec((1, 1, nc, chunk, width),
                            lambda i, j, m: (i, j, m, 0, 0))

    whole = pl.BlockSpec((1, 1, dk, dv), lambda i, j, m: (i, j, 0, 0))
    state, o = pl.pallas_call(
        functools.partial(_kernel, sub=sub),
        grid=(b, h, n // nc),
        in_specs=[at(dk), at(dk), at(dv), at(dk), at(1), whole],
        out_specs=[whole, at(dv)],
        out_shape=[jax.ShapeDtypeStruct((b, h, dk, dv), f32),
                   jax.ShapeDtypeStruct((b, h, n, chunk, dv), f32)],
        scratch_shapes=[pltpu.VMEM((dk, dv), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel', 'arbitrary')),
        interpret=interpret, name='kda_chunk',
    )(*ins, state.astype(f32))
    return state, o.transpose(0, 2, 3, 1, 4).reshape(b, s, h, dv)
