"""Kimi Delta Attention (KDA): a linear-attention mixer whose cache is
not keys and values but a STATE, ``S [H, dk, dv]`` float32 a sequence
(Kimi Linear report, arXiv:2510.26692). The mixer only: parameters'
shapes, the two forms, the state's layout and its masked update. The
layer stack that places it beside latent attention, and the caches
that carry its state, are ``models/mla_moe.py``'s.

Per token ``t`` of one sequence, per head (``dk = dv = kda_head_dim``):

* ``[q~ | k~ | v~] = x W_qkv``; each channel through a causal depthwise
  convolution of width ``kda_conv`` over the sequence, then SiLU;
  ``q, k`` L2-normalised a head, ``q <- q dk^-1/2``.
* decay, a number a CHANNEL: ``g = -exp(A_log_h) softplus((x W_f_a)
  W_f_b + dt_bias)``, ``alpha = exp(g)``; ``beta = sigmoid(x W_beta)``
  a head.
* ``S <- Diag(alpha) S``; ``u = beta (v - S^T k)``; ``S <- S + k u^T``;
  ``o = S^T q``.
* ``y = (RMSNorm_head(o) * sigmoid((x W_g_a) W_g_b)) W_o``.

TWO FORMS of the same recurrence. ``step_layer``: one token a row
(decode), float32 on the VPU: on a TPU ``decode_attention.kda_step``,
which reads and writes the LIVE rows' state once, in place in the
carried [L, B, H, dk, dv] (``step_path`` is the rule); elsewhere
``recur``, plain XLA over the layer's slice (the decayed state's two
products, then the rank-one update). ``forward``: a whole block of
tokens (prefill) in chunks of ``CHUNK``: with ``G`` the decay's running
sum inside a chunk, ``A_ij = sum_c k_ic k_jc exp(G_ic - G_jc)`` (j < i)
makes the chunk's updates the solution of a unit lower-triangular
system, ``(I + Diag(beta) A) U = Diag(beta) (V - (K exp G) S_0)``, which
is solved for ALL chunks at once as ``U = U_0 - W_k S_0`` (the WY
form); only ``S_0 -> U -> O, S_C`` is sequential over the chunks. On
a TPU the same algebra runs as ``ops/kda_prefill.kda_chunk``, a row's
chunks in VMEM and its state in a scratch across them (``chunk_path``
is the rule); elsewhere ``chunked``, plain XLA, all chunks' pairs at
once.
Every exponent taken is of a difference ``G_i - G_j`` with j <= i, so
nothing overflows however fast a channel decays: inside blocks of
``SUB`` positions the pairs are taken exactly, and across blocks both
factors are anchored at the later block's start.

The state's masked update: a position at or past a row's length, and a
row that is not active, takes ``beta = 0`` and ``g = 0``. Then ``u`` is
0 and ``alpha`` 1: the state stays what it was, bit for bit, so a
right-padded prefill leaves each row's state at the row's OWN length
and a freed slot's junk decode step changes nothing. The convolutions'
tails (the last ``kda_conv - 1`` inputs) are taken at the row's length.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from skypilot_tpu.ops import attention as attention_ops
from skypilot_tpu.ops import decode_attention, kda_prefill

Params = Dict[str, Any]
CHUNK = 64      # positions a chunk of the prefill form
SUB = 16        # positions a block inside a chunk (exact pairs)
L2_EPS = 1e-6
_EXACT = jax.lax.Precision.HIGHEST
_F32 = jnp.float32


def channels(cfg) -> int:
    """q, k and v side by side: what the convolutions run over."""
    return 3 * cfg.kda_heads * cfg.kda_head_dim


def state_shape(cfg, rows: int) -> Tuple[int, ...]:
    return (rows, cfg.kda_heads, cfg.kda_head_dim, cfg.kda_head_dim)


def tail_shape(cfg, rows: int) -> Tuple[int, ...]:
    return (rows, cfg.kda_conv - 1, channels(cfg))


def state_bytes_per_row(cfg) -> int:
    """What one KDA layer keeps a sequence, whatever its length."""
    n_state = cfg.kda_heads * cfg.kda_head_dim ** 2
    n_tail = (cfg.kda_conv - 1) * channels(cfg)
    return n_state * 4 + n_tail * jnp.dtype(cfg.dtype).itemsize


def layer_shapes(cfg) -> Dict[str, Tuple[tuple, Any, float]]:
    """``name -> (shape, logical axes, fan_in)`` of one KDA mixer's
    leaves (fan_in 0: a norm's weight)."""
    d, h, dk = cfg.d_model, cfg.kda_heads, cfg.kda_head_dim
    r = cfg.kda_gate_rank
    return {
        'kda_wqkv': ((d, channels(cfg)), ('embed', None), d),
        'kda_conv': ((cfg.kda_conv, channels(cfg)), (None, None),
                     cfg.kda_conv),
        'kda_wf_a': ((d, r), ('embed', None), d),
        'kda_wf_b': ((r, h, dk), (None, 'heads', 'head_dim'), r),
        'kda_a_log': ((h,), (None,), 4.0),
        'kda_dt_bias': ((h, dk), ('heads', 'head_dim'), 1.0),
        'kda_wbeta': ((d, h), ('embed', 'heads'), d),
        'kda_wg_a': ((d, r), ('embed', None), d),
        'kda_wg_b': ((r, h, dk), (None, 'heads', 'head_dim'), r),
        'kda_o_norm': ((dk,), (None,), 0),
        'kda_wo': ((h, dk, d), ('heads', 'head_dim', 'embed'), h * dk)}


# -- what both forms share ----------------------------------------------------


def _l2(x: jax.Array) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def _gates(cfg, h: jax.Array, layer: Params, live: jax.Array):
    """h [..., d], live [...] bool -> (g [..., H, dk] <= 0, beta
    [..., H]), float32, both 0 where not ``live``."""
    with jax.named_scope('kda.gate'):
        f = jnp.einsum('...r,rhk->...hk',
                       jnp.einsum('...d,dr->...r', h, layer['kda_wf_a']),
                       layer['kda_wf_b'], preferred_element_type=_F32)
        a = jnp.exp(layer['kda_a_log'].astype(_F32))[:, None]
        g = -a * jax.nn.softplus(f + layer['kda_dt_bias'].astype(_F32))
        beta = jax.nn.sigmoid(jnp.einsum(
            '...d,dh->...h', h, layer['kda_wbeta'],
            preferred_element_type=_F32))
        return (jnp.where(live[..., None, None], g, 0.0),
                jnp.where(live[..., None], beta, 0.0))


def _qkv(cfg, y: jax.Array):
    """Convolved, activated channels [..., 3*H*dk] -> q, k, v
    [..., H, dk] float32; q and k normalised, q scaled."""
    hd = (cfg.kda_heads, cfg.kda_head_dim)
    q, k, v = (p.reshape(p.shape[:-1] + hd).astype(_F32)
               for p in jnp.split(y, 3, axis=-1))
    return _l2(q) * cfg.kda_head_dim ** -0.5, _l2(k), v


def _out(cfg, o: jax.Array, h: jax.Array, layer: Params) -> jax.Array:
    """o [..., H, dv] float32 -> [..., d]: the head-wise gated norm,
    then ``W_o``."""
    with jax.named_scope('kda.out'):
        gate = jnp.einsum('...r,rhk->...hk',
                          jnp.einsum('...d,dr->...r', h, layer['kda_wg_a']),
                          layer['kda_wg_b'], preferred_element_type=_F32)
        var = jnp.mean(o * o, axis=-1, keepdims=True)
        o = (o * jax.lax.rsqrt(var + cfg.norm_eps)
             * layer['kda_o_norm'].astype(_F32) * jax.nn.sigmoid(gate))
        return jnp.einsum('...hk,hkd->...d', o.astype(h.dtype),
                          layer['kda_wo'])


# -- decode: one token a row --------------------------------------------------


def _path(kernel: str, state_shape, dtype) -> str:
    """``step_path`` and ``chunk_path``: ``paged.decode_path``'s rule,
    a TPU, or the interpreter where a test asks for it by name, and a
    state both kernels take (``decode_attention.kda_fits``: float32,
    whole tiles)."""
    if not (attention_ops._use_pallas()
            or decode_attention.PAGED_INTERPRET):
        return 'xla'
    if decode_attention.kda_fits(state_shape, dtype):
        return 'kernel'
    attention_ops.log_fallback_once(
        kernel, state_shape[-3:],
        f'a {jnp.dtype(dtype).name} state of heads {state_shape[-2:]} '
        f'outside kda_fits()')
    return 'xla'


def step_path(state_shape, dtype) -> str:
    """How the one-token recurrence runs over a state [..., H, dk, dv]:
    ``'kernel'`` (``ops/decode_attention.kda_step``: the live rows'
    state once, in place) or ``'xla'`` (``recur`` over the layer's slice
    of every row), by ``_path``'s rule. The ONE definition:
    ``step_layer`` branches on it and the engine reports it
    (``stats()['kda_step']``)."""
    return _path('kda_step', state_shape, dtype)


def step_inputs(cfg, h: jax.Array, layer: Params, tail: jax.Array,
                live: jax.Array):
    """h [B, d] after ``tail`` [B, cw-1, C] -> (q, k, v, g [B, H, dk],
    beta [B, H], all float32, g and beta 0 where not ``live``; the
    tail, moved on for live rows)."""
    with jax.named_scope('kda.proj'):
        x = jnp.einsum('bd,dc->bc', h, layer['kda_wqkv'])
    with jax.named_scope('kda.conv'):
        window = jnp.concatenate([tail, x[:, None].astype(tail.dtype)], 1)
        y = jnp.einsum('bic,ic->bc', window.astype(_F32),
                       layer['kda_conv'].astype(_F32), precision=_EXACT)
        y = jax.nn.silu(y)
        tail = jnp.where(live[:, None, None], window[:, 1:], tail)
    q, k, v = _qkv(cfg, y)
    g, beta = _gates(cfg, h, layer, live)
    return q, k, v, g, beta, tail


def recur(state: jax.Array, q, k, v, g, beta):
    """The recurrence's one step in plain XLA: state [B, H, dk, dv] ->
    (o [B, H, dv], state). ``g = beta = 0`` leaves a row's state bit
    for bit."""
    with jax.named_scope('kda.step'):
        s = state * jnp.exp(g)[..., None]
        sk = jnp.sum(s * k[..., None], axis=-2)              # S^T k
        sq = jnp.sum(s * q[..., None], axis=-2)              # S^T q
        u = beta[..., None] * (v - sk)
        state = s + k[..., None] * u[..., None, :]
        o = sq + jnp.sum(k * q, axis=-1, keepdims=True) * u
    return o, state


def step_layer(cfg, h: jax.Array, layer: Params, states: jax.Array,
               tails: jax.Array, l, live: jax.Array):
    """One token a row through KDA layer ``l`` (its index among the KDA
    layers; may be traced) of the WHOLE carried ``states`` [L, B, H, dk,
    dv] and ``tails`` [L, B, cw-1, C]: h [B, d] -> (y [B, d], states,
    tails). Rows that are not ``live`` leave state and tail as they
    were. Where ``step_path`` says so the kernel updates the live rows'
    state inside ``states`` (no layer is sliced out or put back)."""
    q, k, v, g, beta, tail = step_inputs(cfg, h, layer, tails[l], live)
    if step_path(states.shape, states.dtype) == 'kernel':
        with jax.named_scope('kda.step'):
            o, states = decode_attention.kda_step(
                states, l, q, k, v, g, beta, live,
                interpret=not attention_ops._use_pallas())
    else:
        o, state = recur(states[l], q, k, v, g, beta)
        states = states.at[l].set(state)
    return _out(cfg, o, h, layer), states, tails.at[l].set(tail)


def step(cfg, h: jax.Array, layer: Params, state: jax.Array,
         tail: jax.Array, live: jax.Array):
    """``step_layer`` over one layer's own state: h [B, d] -> (y [B, d],
    state [B, H, dk, dv], tail [B, cw-1, C])."""
    y, states, tails = step_layer(cfg, h, layer, state[None], tail[None], 0,
                                  live)
    return y, states[0], tails[0]


# -- prefill: chunks ----------------------------------------------------------


def _conv(cfg, x: jax.Array, layer: Params, tail: jax.Array,
          row_lens: jax.Array):
    """x [B, S, C] after ``tail`` [B, cw-1, C] -> (SiLU(conv) [B, S, C]
    float32, the tail at each row's length)."""
    with jax.named_scope('kda.conv'):
        cw, s = cfg.kda_conv, x.shape[1]
        padded = jnp.concatenate([tail, x.astype(tail.dtype)], 1)
        w = layer['kda_conv'].astype(_F32)
        y = sum(padded[:, i:i + s].astype(_F32) * w[i] for i in range(cw))
        tail = jax.vmap(lambda p, n: jax.lax.dynamic_slice_in_dim(
            p, n, cw - 1, 0))(padded, row_lens)
        return jax.nn.silu(y), tail


def _unit_lower_inverse(m: jax.Array) -> jax.Array:
    """Inverse of ``I + N``, N = ``m``'s strictly lower part, [..., n,
    n]: forward substitution inside blocks of ``SUB`` rows (unrolled:
    exact and stable, where the Neumann product's powers cancel), the
    2 x 2 block formula above them."""
    n = m.shape[-1]
    if n <= SUB:
        x = jnp.broadcast_to(jnp.eye(n, dtype=m.dtype), m.shape)
        for i in range(1, n):
            row = jnp.einsum('...j,...jk->...k', m[..., i, :i],
                             x[..., :i, :], precision=_EXACT)
            x = x.at[..., i, :].add(-row)
        return x
    half = n // 2
    a = _unit_lower_inverse(m[..., :half, :half])
    d = _unit_lower_inverse(m[..., half:, half:])
    c = -jnp.einsum('...ij,...jk,...kl->...il', d, m[..., half:, :half], a,
                    precision=_EXACT)
    zero = jnp.zeros(a.shape[:-1] + (n - half,), m.dtype)
    return jnp.concatenate([jnp.concatenate([a, zero], -1),
                            jnp.concatenate([c, d], -1)], -2)


def _decayed_pairs(x: jax.Array, k: jax.Array, gc: jax.Array) -> jax.Array:
    """``P_ij = sum_c x_ic k_jc exp(G_ic - G_jc)`` for j <= i, 0 above
    the diagonal. x, k, gc [..., T, dk] (T a chunk, ``gc`` the running
    sum of g inside it) -> [..., T, T]."""
    t, dk = x.shape[-2:]
    nb = t // SUB
    lead = x.shape[:-2]
    blk = lambda a: a.reshape(lead + (nb, SUB, dk))       # noqa: E731
    xb, kb, gb = blk(x), blk(k), blk(gc)
    # inside a block: every pair exactly
    i = jnp.arange(SUB)
    diff = gb[..., :, None, :] - gb[..., None, :, :]      # [.., nb, i, j, c]
    keep = (i[:, None] >= i[None, :])[..., None]
    inner = jnp.sum(xb[..., :, None, :] * kb[..., None, :, :]
                    * jnp.exp(jnp.where(keep, diff, -jnp.inf)), axis=-1)
    # across blocks: both factors anchored at the later block's start
    # (the sum just before it), so each is a decay, never a growth
    anchor = jnp.concatenate(
        [jnp.zeros_like(gb[..., :1, 0, :]), gb[..., :-1, SUB - 1, :]], -2)
    left = xb * jnp.exp(gb - anchor[..., :, None, :])     # [.., nb, i, c]
    before = (jnp.arange(t)[None, :] < (jnp.arange(nb) * SUB)[:, None])
    right = k[..., None, :, :] * jnp.exp(jnp.where(
        before[..., None], anchor[..., :, None, :] - gc[..., None, :, :],
        -jnp.inf))                                        # [.., nb, T, c]
    outer = jnp.einsum('...aic,...ajc->...aij', left, right,
                       precision=_EXACT)                  # [.., nb, SUB, T]
    outer = outer.reshape(lead + (nb, SUB, nb, SUB))
    same = jnp.eye(nb, dtype=bool)[:, None, :, None]
    return jnp.where(same, inner[..., :, :, None, :],
                     outer).reshape(lead + (t, t))


def _chunks(q, k, v, g, beta):
    """A row's chunk (``CHUNK``, or the row in whole blocks when it is
    shorter) and q, k, v, g, beta padded to a whole number of chunks:
    masked positions, g = beta = 0, change nothing."""
    s = q.shape[1]
    chunk = min(CHUNK, -(-s // SUB) * SUB)
    pad = -s % chunk
    return chunk, tuple(jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),)
                                * (a.ndim - 2))
                        for a in (q, k, v, g, beta))


def chunked(q, k, v, g, beta, state):
    """The recurrence over a block of positions. q, k, v, g [B, S, H,
    dk] and beta [B, S, H] float32 (g, beta 0 at masked positions),
    state [B, H, dk, dv] -> (o [B, S, H, dv], state)."""
    b, s, h, dk = q.shape
    chunk, (q, k, v, g, beta) = _chunks(q, k, v, g, beta)
    n = q.shape[1] // chunk
    # [B, S, H, x] -> [B, H, N, T, x]
    cut = lambda a: a.reshape(b, n, chunk, h, -1).transpose(0, 3, 1, 2, 4)  # noqa: E731,E501
    q, k, v, g = cut(q), cut(k), cut(v), cut(g)
    beta = cut(beta[..., None])
    gc = jnp.cumsum(g, axis=-2)
    a_kk = _decayed_pairs(k, k, gc)
    strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    inv = _unit_lower_inverse(jnp.where(strict, beta * a_kk, 0.0))
    a_qk = _decayed_pairs(q, k, gc)
    decay = jnp.exp(gc)
    u0 = jnp.einsum('...ij,...jv->...iv', inv, beta * v, precision=_EXACT)
    wk = jnp.einsum('...ij,...jc->...ic', inv, beta * k * decay,
                    precision=_EXACT)
    last = gc[..., -1:, :]
    xs = (u0, wk, q * decay, a_qk, k * jnp.exp(last - gc),
          jnp.exp(last[..., 0, :]))

    def one(state, x):
        u0, wk, qd, a_qk, kd, dlast = x
        u = u0 - jnp.einsum('bhic,bhcv->bhiv', wk, state, precision=_EXACT)
        o = (jnp.einsum('bhic,bhcv->bhiv', qd, state, precision=_EXACT)
             + jnp.einsum('bhij,bhjv->bhiv', a_qk, u, precision=_EXACT))
        state = (state * dlast[..., None]
                 + jnp.einsum('bhic,bhiv->bhcv', kd, u, precision=_EXACT))
        return state, o

    state, o = jax.lax.scan(one, state,
                            jax.tree.map(lambda a: jnp.moveaxis(a, 2, 0), xs))
    o = jnp.moveaxis(o, 0, 2).transpose(0, 2, 3, 1, 4)     # [B, N, T, H, dv]
    return o.reshape(b, n * chunk, h, -1)[:, :s], state


def chunk_path(state_shape, dtype) -> str:
    """How the chunked recurrence of a prefill runs over a state [...,
    H, dk, dv]: ``'kernel'`` (``ops/kda_prefill.kda_chunk``: a head's
    chunks in VMEM, its state in a scratch across them) or ``'xla'``
    (``chunked``), by ``step_path``'s rule. The ONE definition:
    ``forward`` branches on it and the engine reports it
    (``stats()['kda_chunk']``)."""
    return _path('kda_chunk', state_shape, dtype)


def chunked_kernel(q, k, v, g, beta, state):
    """``chunked``'s arguments and result through ``kda_chunk``: the
    same chunks (and the same padding of a row to a whole number of
    them), the state continued in VMEM."""
    chunk, padded = _chunks(q, k, v, g, beta)
    state, o = kda_prefill.kda_chunk(
        *padded, state, chunk=chunk, sub=SUB,
        interpret=not attention_ops._use_pallas())
    return o[:, :q.shape[1]], state


def forward(cfg, h: jax.Array, layer: Params, state: jax.Array,
            tail: jax.Array, row_lens: jax.Array,
            live: Optional[jax.Array] = None):
    """h [B, S, d] continuing ``state`` / ``tail``; the row's first
    ``row_lens[b]`` positions are real. -> (y [B, S, d], state, tail),
    both at the row's own length. The rows of a group go ONE AT A TIME
    (``lax.map``): a row of thousands of tokens has 32 heads x its
    chunks to keep the chip busy, and the float32 temporaries of the
    chunked form (a dozen of [S, H, dk]) are then one row's, not the
    group's (a 4 x 4096 prefill compiles to 4.5 GB of temporaries
    that way, 1.9 this way). The recurrence of a row runs where
    ``chunk_path`` says: ``kda_chunk``, or ``chunked``."""
    if live is not None:
        row_lens = jnp.where(live, row_lens, 0)

    def rows(h, state, tail, row_lens):
        real = jnp.arange(h.shape[1])[None, :] < row_lens[:, None]
        with jax.named_scope('kda.proj'):
            x = jnp.einsum('bsd,dc->bsc', h, layer['kda_wqkv'])
        y, tail = _conv(cfg, x, layer, tail, row_lens)
        q, k, v = _qkv(cfg, y)
        g, beta = _gates(cfg, h, layer, real)
        with jax.named_scope('kda.chunk'):
            if chunk_path(state.shape, state.dtype) == 'kernel':
                o, state = chunked_kernel(q, k, v, g, beta, state)
            else:
                o, state = chunked(q, k, v, g, beta, state)
        return _out(cfg, o, h, layer), state, tail

    return jax.lax.map(
        lambda a: jax.tree.map(lambda x: x[0],
                               rows(*(x[None] for x in a))),
        (h, state, tail, row_lens))
