"""The plain reference: a Llama-family decoder in straightforward
``jax.numpy``, float32, ``highest`` matmul precision, no kernels, no
cache, no batching tricks. It imports nothing of the program.

Published description followed (InternLM2 / Mistral are both this
block): pre-norm RMSNorm; rotary embedding in the rotate-half
convention over the whole head; grouped-query attention where query
head ``h`` reads key/value head ``h // (Hq / Hkv)``; causal softmax
attention scaled by ``1/sqrt(D)``; SwiGLU feed-forward
``down(silu(gate(x)) * up(x))``; untied output head. Departure: the
weights arrive stacked by layer in the program's layout (see
``benchmarks/weights.py``) and are cast from bfloat16 to float32 one
layer at a time, so that the whole model never exists in float32.

``quant='int8'`` is the CONTROL: the same mathematics with every
matrix product (projections, feed-forward, output head, and the two
products inside attention) computed on int8 operands — activations
quantized per row, weights per output channel, symmetric, round to
nearest — which is the precision below the bfloat16 the configurations
state. It must come out as not correct.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _q8(x: jax.Array, axis: int) -> jax.Array:
    """Symmetric int8 fake-quantization along ``axis`` (the contracted
    one): the values an int8 product would see, kept in float32."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    q = jnp.clip(jnp.round(x / scale), -127, 127) * scale
    # Straight-through: the backward pass sees the identity, as int8
    # training does (rounding itself has no gradient), and its products
    # run on the quantized operands the forward pass kept.
    return x + jax.lax.stop_gradient(q - x)


def _mm(x, w, spec: str, quant: Optional[str], x_axis: int = -1,
        w_axes=(0,)):
    """``einsum(spec, x, w)`` at highest precision; under the control,
    on int8 operands (x per row over its contracted axis, w per output
    channel over its contracted axes)."""
    if quant == 'int8':
        x, w = _q8(x, x_axis), _q8(w, w_axes)
    elif quant is not None:
        raise ValueError(f'unknown control precision {quant!r}')
    return jnp.einsum(spec, x, w, precision=HIGHEST)


def rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def rope(x, positions, theta):
    """x [T, H, D], positions [T]; rotate-half convention."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attend_group(q, k, v, quant):
    """One key/value head with its group of query heads.
    q [G, T, D], k/v [T, D] -> [G, T, D]; causal."""
    t, d = k.shape
    qs, ks = (_q8(q, -1), _q8(k, -1)) if quant == 'int8' else (q, k)
    s = jnp.einsum('gtd,ud->gtu', qs, ks, precision=HIGHEST) / (d ** 0.5)
    mask = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(mask[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    if quant == 'int8':
        p, v = _q8(p, -1), _q8(v, 0)
    return jnp.einsum('gtu,ud->gtd', p, v, precision=HIGHEST)


def layer(x, w: Dict[str, Any], positions, cfg: Dict[str, Any],
          quant: Optional[str] = None):
    """One decoder block on one sequence. x [T, d] float32."""
    eps, theta = cfg['rms_norm_eps'], cfg['rope_theta']
    w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    h = rms_norm(x, w['attn_norm'], eps)
    q = _mm(h, w['wq'], 'td,dhk->thk', quant)
    k = _mm(h, w['wk'], 'td,dhk->thk', quant)
    v = _mm(h, w['wv'], 'td,dhk->thk', quant)
    q, k = rope(q, positions, theta), rope(k, positions, theta)
    t, hq, d = q.shape
    hkv = k.shape[1]
    qg = q.transpose(1, 0, 2).reshape(hkv, hq // hkv, t, d)
    kg, vg = k.transpose(1, 0, 2), v.transpose(1, 0, 2)
    # One key/value head at a time, rematerialized in the backward
    # pass: the [G, T, T] scores of one group are all that is alive.
    att = jax.lax.map(
        jax.checkpoint(lambda a: _attend_group(a[0], a[1], a[2], quant)),
        (qg, kg, vg))
    att = att.reshape(hq, t, d).transpose(1, 0, 2)
    x = x + _mm(att, w['wo'], 'thk,hkd->td', quant, x_axis=(-2, -1),
                w_axes=(0, 1))
    h = rms_norm(x, w['mlp_norm'], eps)
    gate = _mm(h, w['w_gate'], 'td,df->tf', quant)
    up = _mm(h, w['w_up'], 'td,df->tf', quant)
    return x + _mm(jax.nn.silu(gate) * up, w['w_down'], 'tf,fd->td', quant)


def hidden(params, tokens, cfg: Dict[str, Any],
           quant: Optional[str] = None, remat: bool = False):
    """tokens [T] -> final-norm hidden states [T, d] float32."""
    positions = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    x = params['embed'][tokens].astype(jnp.float32)

    def body(x, w):
        return layer(x, w, positions, cfg, quant), None

    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, params['layers'])
    return rms_norm(x, params['final_norm'].astype(jnp.float32),
                    cfg['rms_norm_eps'])


def cfg_items(cfg: Dict[str, Any]) -> tuple:
    """The configuration's numbers as a hashable, for jit's static
    arguments."""
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float)) and v is not None))


@functools.partial(jax.jit, static_argnames=('cfg_items', 'quant'))
def _logits_at(params, tokens, rows, cfg_items, quant):
    cfg = dict(cfg_items)
    h = hidden(params, tokens, cfg, quant)[rows]
    return _mm(h, params['lm_head'].astype(jnp.float32), 'td,dv->tv', quant)


def logits_at(params, tokens, rows, cfg: Dict[str, Any],
              quant: Optional[str] = None):
    """Logits [len(rows), V] of one sequence at positions ``rows``
    (row ``i`` predicts token ``i + 1``). ``tokens`` may be padded on
    the right: causality keeps the padding out of earlier rows."""
    return _logits_at(params, jnp.asarray(tokens, jnp.int32),
                      jnp.asarray(rows, jnp.int32), cfg_items(cfg), quant)
